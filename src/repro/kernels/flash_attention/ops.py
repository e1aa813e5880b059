"""Public op: flash attention accepting the model's (B,S,H,D) layout."""
from __future__ import annotations

from repro.kernels import interpret_mode
from repro.kernels.flash_attention.flash_attention import flash_attention_bhsd


def flash_attention(q, k, v, *, causal: bool = True, q_chunk: int = 512,
                    kv_chunk: int = 512):
    """q,k,v: (B, S, H, D) with kv already expanded to H heads."""
    qt = q.swapaxes(1, 2)
    kt = k.swapaxes(1, 2)
    vt = v.swapaxes(1, 2)
    out = flash_attention_bhsd(qt, kt, vt, causal=causal, q_chunk=q_chunk,
                               kv_chunk=kv_chunk, interpret=interpret_mode())
    return out.swapaxes(1, 2)
