"""``chacha20_xor_rows`` (``repro.kernels.chacha20``): per-row ChaCha20
keystream XOR over (R, 16) u32 rows, each row with its own key (R, 8),
nonce (R, 3) and counter (R, 1).  The batched seal and open of every
sealed edge run it over a window's blocks plus one MAC-key block per chunk.

One call needs, at least, every operand read once and the result written
once: R * (8 + 3 + 1 + 16 + 16) * 4 bytes."""
import re

from streambench.devicetrace import nbytes, shapes

PATTERN = re.compile(r"^%chacha20_xor_rows(\.\d+)? = ")


def hbm_bytes(text: str):
    s = shapes(text)
    if len(s) != 5:
        return None
    (_, out), keys, nonces, ctrs, data = s
    R = out[0]
    if out != (R, 16) or keys[1] != (R, 8) or nonces[1] != (R, 3) \
            or ctrs[1] != (R, 1) or data[1] != (R, 16):
        return None
    return sum(nbytes(dt, dims) for dt, dims in s)


def calls_per_window(config) -> int:
    """The least calls per engine window that the configuration's
    guarantees need: in a sealed mode, the ingress seal and the sink's
    open."""
    return 0 if config["mode"] == "plain" else 2
