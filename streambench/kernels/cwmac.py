"""``mac_partials_batch`` (``repro.kernels.cwmac``): the Carter-Wegman
MAC's tiled partials.  Limbs (B, N) u32 are multiplied by each row's
powers (B, tile) and folded to (B, N / tile * 128) lane partials.  Every
seal, open and enclave-hop MAC of a window runs it (both keys of the dual
MAC as 2B rows).

One call needs, at least, the limbs and the powers read once and the
partials written once: 4 * (B * N + B * tile + B * N / tile * 128) bytes."""
import re

from streambench.devicetrace import nbytes, shapes

PATTERN = re.compile(r"^%mac_partials_batch(\.\d+)? = ")


def hbm_bytes(text: str):
    s = shapes(text)
    if len(s) != 3:
        return None
    (_, out), (_, limbs), (_, powers) = s
    B, N = limbs
    tile = powers[1]
    if powers[0] != B or N % tile or out != (B, N // tile * 128):
        return None
    return sum(nbytes(dt, dims) for dt, dims in s)


def calls_per_window(config) -> int:
    """The least calls per engine window that the configuration's
    guarantees need: in a sealed mode, the ingress tags, each hop's check
    of its inbound tags, and the sink's check."""
    return 0 if config["mode"] == "plain" \
        else 2 + len(config["job"]["stages"])
