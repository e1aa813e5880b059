"""End-to-end driver: the paper's DelayedFlights macro-benchmark (§5.2).

Computes per-carrier average delay + delayed-flight counts over a synthetic
BTS-style stream under any of the three Fig.-6 security configurations,
with elastic per-stage worker scaling — declared in a few lines via the
fluent DSL (``repro.dsl``; pass ``--spec`` to load the equivalent TOML
spec instead).  See docs/dsl.md for the Listing-1/Listing-2 mapping.

Run:  PYTHONPATH=src python examples/flight_delay_pipeline.py \
          --mode enclave --workers 2 --records 65536
"""
import argparse
import os
import time

import jax.numpy as jnp

from repro.data.synthetic import flight_chunks
from repro.dsl import load_spec, stream
from repro.launch.cache import enable_compile_cache

CARRIERS = 20

SPEC_PATH = os.path.join(os.path.dirname(__file__), "flight_delay.toml")


def build_pipeline(mode: str, workers: int):
    """The paper's Listing-1 job, fluent form.  The TOML spec next to
    this file is the declarative equivalent: both compile through the
    same validator/fusion path and produce bit-identical results
    (stage *structure* can differ only where fusion rules apply)."""
    return (stream()
            .map("identity", name="sgx_mapper", workers=workers, sgx=True)
            .filter("delay_filter_u32", const=15, name="sgx_filter",
                    workers=workers, sgx=True)
            .reduce("carrier_delay_stats", name="reducer")
            .secure(mode))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="enclave",
                    choices=["plain", "encrypted", "enclave"])
    ap.add_argument("--workers", type=int, default=1)
    ap.add_argument("--records", type=int, default=65_536)
    ap.add_argument("--chunk", type=int, default=1024)
    ap.add_argument("--spec", action="store_true",
                    help=f"build from the TOML spec ({SPEC_PATH}) instead "
                         f"of the fluent chain")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="record per-window spans and write a Chrome-trace "
                         "JSON here (open in chrome://tracing / Perfetto)")
    ap.add_argument("--serve-metrics", metavar="PORT", type=int,
                    default=None,
                    help="attach a live PipelineMonitor and serve "
                         "/metrics (Prometheus), /health and /snapshot "
                         "on this port while the job streams (0 = pick "
                         "an ephemeral port)")
    ap.add_argument("--serve-hold", metavar="SECONDS", type=float,
                    default=0.0,
                    help="with --serve-metrics: keep the endpoint up this "
                         "long after the run so scrapers can collect the "
                         "final snapshot (CI uses this)")
    args = ap.parse_args()
    enable_compile_cache()

    if args.spec:
        pipe = (load_spec(SPEC_PATH).secure(args.mode)
                .scale("sgx_mapper", args.workers)
                .scale("sgx_filter", args.workers))
    else:
        pipe = build_pipeline(args.mode, args.workers)
    if args.trace:
        pipe = pipe.trace()
    srv = None
    if args.serve_metrics is not None:
        from repro.obs.export import serve_metrics
        pipe = pipe.monitor()
        srv = serve_metrics(args.serve_metrics,
                            monitor=pipe.health_monitor)
        print(f"live health: {srv.url}/metrics {srv.url}/health "
              f"{srv.url}/snapshot", flush=True)
    src = (jnp.asarray(c) for c in
           flight_chunks(args.records, args.chunk * args.workers, seed=1))
    t0 = time.perf_counter()
    out = pipe.run(src)
    dt = time.perf_counter() - t0
    mb = args.records * 64 / 1e6

    print(f"mode={args.mode} workers={args.workers} "
          f"records={args.records} ({mb:.1f} MB)")
    print(f"pipeline: {pipe.describe()}")
    print(f"completed in {dt:.2f}s  ({mb / dt:.2f} MB/s)")
    print(f"{'carrier':>8} {'delayed':>9} {'avg delay':>10}")
    for c in range(CARRIERS):
        n = int(out["count"][c])
        avg = out["sum"][c] / max(n, 1)
        print(f"{c:>8} {n:>9} {avg:>9.1f}m")
    print("stage report:")
    for name, rep in pipe.report().items():
        print(f"  {name:12s} {rep}")
    if args.trace:
        pipe.tracer.export_chrome(args.trace)
        print(f"wrote {args.trace} ({len(pipe.tracer)} spans) — open in "
              f"chrome://tracing or https://ui.perfetto.dev")
    if srv is not None:
        snap = pipe.health_monitor.snapshot()
        print(f"monitor: {snap['pipeline']['windows_total']} windows, "
              f"{snap['pipeline']['dispatches']} device dispatches, "
              f"stages={sorted(snap['stages'])}")
        if args.serve_hold:
            print(f"holding metrics endpoint {args.serve_hold:.0f}s for "
                  f"scrapers...", flush=True)
            time.sleep(args.serve_hold)
        srv.stop()


if __name__ == "__main__":
    main()
