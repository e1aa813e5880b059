"""Readings behind the limits of ``correct``: the program's runs and the
control's, on several seeds, in one process on the chip.

    python3 streambench/control.py --workload flights-enclave.saturate \
        --seeds 11,12,13 --seconds 10 [--control-only]

For each seed it runs the cell as the benchmark does and then the control:
the same run with a sink that skips the last chunk of every engine window
(at-most-once delivery, breaking the configurations' exactly-once
guarantee).  Each run prints its seed and every number compared.  The
benchmark's own runs never run the control.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from streambench.run import OUT_DIR, open_chip  # noqa: E402


def skip_last_of_window(win: int):
    """The control's sink: skips the last chunk of every window."""
    return lambda i: i % win == win - 1


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
    legs = [("control", skip_last_of_window(win))]
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
