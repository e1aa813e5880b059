"""``enclave_apply_rows`` (``repro.kernels.enclave_map``): the enclave
hop's fused decrypt -> operator -> re-encrypt over (R, 16) u32 ciphertext
rows, each row with its own inbound and outbound key (R, 8), inbound and
outbound nonce (R, 3) and counter (R, 1).

One call needs, at least, every operand read once and the result written
once: R * (8 + 8 + 3 + 1 + 3 + 1 + 16 + 16) * 4 bytes."""
import re

from streambench.devicetrace import nbytes, shapes

PATTERN = re.compile(r"^%enclave_apply_rows(\.\d+)? = ")
_COLS = (8, 8, 3, 1, 3, 1, 16)


def hbm_bytes(text: str):
    s = shapes(text)
    if len(s) != 8:
        return None
    R = s[0][1][0]
    if s[0][1] != (R, 16) or \
            tuple(dims for _, dims in s[1:]) != tuple((R, c) for c in _COLS):
        return None
    return sum(nbytes(dt, dims) for dt, dims in s)


def calls_per_window(config) -> int:
    """The least calls per engine window that the configuration's
    guarantees need: in enclave mode, one for each hop whose stage runs in
    the enclave, the only place its plaintext may exist."""
    if config["mode"] != "enclave":
        return 0
    return sum(1 for st in config["job"]["stages"] if st.get("sgx"))
