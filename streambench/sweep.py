"""Find a configuration's knee: open-loop runs at fixed chunk rates, one
process, one chip.

    python3 streambench/sweep.py --config flights-enclave \
        --rates 80,90,100 --seconds 15 --seed 7

For each rate it prints the chunks' source queue wait (due -> taken by the
engine) at the 95th percentile over each third of the window, its growth
(the least-squares slope of every chunk's wait against its due time, ms per
second), and the latency.  Above capacity the backlog, and so the wait,
grows all through the window, by 1000 * (1 - capacity / rate) ms per
second; below it the wait does not grow.  The knee is the highest rate
whose wait does not grow.  Not run by the benchmark's cells.
"""
import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from streambench.run import OUT_DIR, open_chip  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    peaks = open_chip()
    from streambench import harness, layout
    from streambench.stats import percentile
    bench = layout.load_benchmark(ROOT)
    entry = next(c for c in bench["configs"] if c["name"] == args.config)
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        cell = layout.Cell(name=f"{args.config}.sweep", config=config,
                           traffic={"arrival": "poisson",
                                    "chunks_per_s": rate},
                           chips=1, end_to_end=[], per_layer=[])
        out = harness.run_cell(cell, args.seed + i, args.seconds, False,
                               peaks=peaks, t_process=time.perf_counter(),
                               out_dir=OUT_DIR)
        run = out.run
        wait = (run.take - run.due) * 1e3
        third = len(wait) // 3
        n = min(run.folded, run.offered)
        print(json.dumps({
            "rate_chunks_per_s": rate, "correct": out.correct,
            "chunks": run.offered,
            "queue_wait_p95_ms_by_third": [
                percentile(wait[j * third:(j + 1) * third], 95)
                for j in range(3)],
            "queue_wait_p95_ms": percentile(wait, 95),
            "queue_wait_growth_ms_per_s": float(
                np.polyfit(run.due - run.due[0], wait, 1)[0]),
            "latency_p50_ms": percentile(
                (run.fold[:n] - run.due[:n]) * 1e3, 50),
            "latency_p95_ms": percentile(
                (run.fold[:n] - run.due[:n]) * 1e3, 95),
            "records_per_s": run.folded * int(config["chunk_records"])
            / (run.t_end - run.t_open)}), flush=True)


if __name__ == "__main__":
    main()
