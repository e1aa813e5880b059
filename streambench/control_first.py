"""The control that skips the first chunk of every engine window, run on
the chip beside the program, in one process.

    python3 streambench/control_first.py --workload ysb-enclave.saturate \
        --seeds 11,12 --seconds 10 [--control-only]

A count stage writes each worker share's partial-count rows at the first
row of each key, nearly always in the share's first chunk, so the last
chunk of a window carries no counts there and ``control.py`` (which skips
it) leaves the result right.  Skipping each window's first chunk drops
those partial rows: at-most-once delivery again, and the configuration's
exactly-once guarantee broken where its counts sit.  Each run prints its
seed and every number compared.  The benchmark's own runs never run a
control.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from streambench.run import OUT_DIR, open_chip  # noqa: E402


def skip_first_of_window(win: int):
    """The control's sink: skips the first chunk of every window."""
    return lambda i: i % win == 0


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control-only", action="store_true")
    args = ap.parse_args(argv)
    peaks = open_chip()
    from streambench import harness, layout
    cell = layout.resolve(layout.load_benchmark(ROOT), args.workload)
    win = harness.window_chunks(cell.config)
    legs = [("control_first", skip_first_of_window(win))]
    if not args.control_only:
        legs.insert(0, ("program", None))
    for seed in (int(s) for s in args.seeds.split(",")):
        for leg, skip in legs:
            out = harness.run_cell(cell, seed, args.seconds, False,
                                   peaks=peaks,
                                   t_process=time.perf_counter(),
                                   out_dir=OUT_DIR, skip_fold=skip)
            print(json.dumps({"workload": cell.name, "seed": seed,
                              "leg": leg, "correct": out.correct,
                              "attempted": out.attempted,
                              "checks": out.checks}), flush=True)


if __name__ == "__main__":
    main()
