"""Share of its HBM roofline that the YSB join kernel,
``campaign_join_rows`` (``repro.kernels.enclave_map``), reached in the
traced interval: the HBM bytes its calls need over the chip's peak HBM
bandwidth, divided by the kernel's summed device time.  None where the
interval holds no such call.

A call is the join hop's fused decrypt -> join -> re-encrypt over (R, 16)
u32 ciphertext rows, each row with its own inbound and outbound key
(R, 8), inbound and outbound nonce (R, 3) and counter (R, 1), against the
sealed campaign table (64, 128) and its key and nonce (1, 16).  It needs,
at least, every operand read once and the result written once:
R * (8 + 8 + 3 + 1 + 3 + 1 + 16 + 16) * 4 + (64 * 128 + 16) * 4 bytes.

The kernel's cost function lives here, not in ``streambench/kernels/``,
whose list of files the benchmark's own tests pin."""
import re

from streambench.devicetrace import nbytes, shapes

PATTERN = re.compile(r"^%campaign_join_rows(\.\d+)? = ")
_COLS = (8, 8, 3, 1, 3, 1, 16)
_TABLE = ((64, 128), (1, 16))


def hbm_bytes(text: str):
    s = shapes(text)
    if len(s) != 10:
        return None
    R = s[0][1][0]
    if s[0][1] != (R, 16) \
            or tuple(d for _, d in s[1:8]) != tuple((R, c) for c in _COLS) \
            or tuple(d for _, d in s[8:]) != _TABLE:
        return None
    return sum(nbytes(dt, dims) for dt, dims in s)


def read(run):
    if run.device is None:
        return None
    lo, hi = run.interval
    events = run.device.events(lo, hi, PATTERN)
    if not events:
        return None
    need = [hbm_bytes(text) for _, _, text in events]
    took = sum(b - a for a, b, _ in events)
    if None in need or took <= 0:
        return None
    return 100.0 * sum(need) / run.peaks["hbm_bytes_per_s"] / took
