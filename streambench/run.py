"""Run one benchmark cell once and print its result line.

    python3 streambench/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout: BENCHMARK.json names the cells, and the
program under test is ``src/repro``.  The run refuses to start unless JAX's
first device is a TPU of a kind in ``streambench/peaks.json`` and there are
as many chips as the cell asks for.  With ``--trace 0`` the line holds the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics, read
from a profiled run.  The compile cache is ``<checkout>/.jax_cache``.
Diagnostics, and last the numbers that decided ``correct`` beside their
limits, go to standard error; the last line of standard output is the
result, one JSON object.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
OUT_DIR = os.path.join(ROOT, "streambench", "out")
# The compile cache lives at a fixed path inside the checkout, keeps every
# program however small or quick to compile, and never evicts.  JAX reads
# these when it is imported.
JAX_ENV = {"JAX_COMPILATION_CACHE_DIR": CACHE_DIR,
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
           "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "0",
           "JAX_COMPILATION_CACHE_MAX_SIZE": "-1"}


def fail(msg: str) -> None:
    print(f"streambench: {msg}", file=sys.stderr)
    raise SystemExit(2)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def open_chip() -> dict:
    """Check the device and set up the compile cache; returns the device
    kind's row of the peak table.  Exits, printing no result, unless JAX's
    first device is a TPU of a kind in the table."""
    os.environ.update(JAX_ENV)
    # the TPU runtime's own logs would go to a fixed path outside the
    # checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"JAX's first device is {devices[0].platform!r}, not a TPU")
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        fail(f"no program under test at {ROOT}/src/repro")
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    from streambench import layout
    try:
        peaks = layout.peaks(devices[0].device_kind)
    except KeyError as e:
        fail(str(e))
    print(f"{len(devices)} x {devices[0].device_kind}, jax {jax.__version__}"
          f", devices up {time.perf_counter() - T_PROCESS} s after start, "
          f"compile cache {jax.config.jax_compilation_cache_dir}",
          file=sys.stderr)
    return peaks


def main(argv=None) -> None:
    args = parse(argv)
    peaks = open_chip()
    import jax
    from streambench import harness, layout
    cell = layout.resolve(layout.load_benchmark(ROOT), args.workload)
    if len(jax.devices()) < cell.chips:
        fail(f"{cell.name} needs {cell.chips} chips, found "
             f"{len(jax.devices())}")
    out_dir = os.path.join(OUT_DIR, f"{cell.name}.{args.seed}")
    os.makedirs(out_dir, exist_ok=True)
    print(f"{cell.name} seed {args.seed} {args.seconds} s trace "
          f"{args.trace}", file=sys.stderr)
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           peaks=peaks, t_process=T_PROCESS,
                           out_dir=out_dir)
    for k, c in out.checks.items():
        print(f"check {k} {c['value']} limit {c['limit']}", file=sys.stderr)
    print(json.dumps(out.line()))


if __name__ == "__main__":
    main()
