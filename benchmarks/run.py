"""Benchmark harness: one module per paper table/figure.

Usage: PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME]
                                               [--json [PATH]]
Output: CSV rows ``name,us_per_call,derived``; with ``--json`` also a
machine-readable ``BENCH_<name>.json`` artifact for the CI perf trajectory.
"""
from __future__ import annotations

import argparse
import ast
import importlib.util
import json
import platform
import sys
import traceback

from benchmarks.common import bench_meta, emit

MODULES = [
    ("ecall", "benchmarks.bench_ecall"),                 # §5.3 µbench 1
    ("chunk_copy", "benchmarks.bench_chunk_copy"),       # Fig. 4
    ("enclave_compute", "benchmarks.bench_enclave_compute"),  # Fig. 5 / T.2
    ("pipeline", "benchmarks.bench_pipeline_throughput"),     # Fig. 6
    ("scaling_stages", "benchmarks.bench_scaling_stages"),    # Fig. 7
    ("scaling_mappers", "benchmarks.bench_scaling_mappers"),  # Fig. 8
    ("dist", "benchmarks.bench_dist"),                   # repro.dist layer
    ("aead", "benchmarks.bench_aead"),                   # ISSUE 2 fast path
    ("attest", "benchmarks.bench_attest"),               # ISSUE 3 lifecycle
    ("loc", "benchmarks.bench_loc"),                     # Table 1
    ("kernels", "benchmarks.bench_kernels"),             # beyond-paper
    ("roofline", "benchmarks.bench_roofline"),           # §Roofline table
]


def _bench_descriptions() -> str:
    """One line per registered bench, sourced from each module's
    docstring (ast-parsed from source — no jax import just for --help)."""
    lines = ["registered benchmarks:"]
    for name, mod in MODULES:
        try:
            spec = importlib.util.find_spec(mod)
            with open(spec.origin, "r") as f:
                doc = ast.get_docstring(ast.parse(f.read())) or ""
            first = doc.strip().splitlines()[0] if doc.strip() else \
                "(no module docstring)"
        except Exception as e:                      # noqa: BLE001
            first = f"(unreadable: {e})"
        lines.append(f"  {name:16s} {first}")
    return "\n".join(lines)


def main() -> None:
    ap = argparse.ArgumentParser(
        formatter_class=argparse.RawDescriptionHelpFormatter,
        epilog=_bench_descriptions())
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="alias for --quick (CI smoke pass)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--skip", default=None,
                    help="comma-separated module names to skip")
    ap.add_argument("--json", nargs="?", const="auto", default=None,
                    metavar="PATH",
                    help="also write a JSON artifact (default path "
                         "BENCH_<only|all>.json)")
    args = ap.parse_args()
    args.quick = args.quick or args.smoke
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    failed = 0
    collected = []
    skips = set((args.skip or "").split(","))
    for name, mod in MODULES:
        if args.only and args.only != name:
            continue
        if name in skips:
            continue
        try:
            m = __import__(mod, fromlist=["run"])
            rows = m.run(quick=args.quick)
            emit(rows)
            collected += [{"bench": name, "name": r[0], "us_per_call": r[1],
                           "derived": r[2]} for r in rows]
        except Exception:
            failed += 1
            print(f"{name},0.0,BENCH-ERROR", file=sys.stdout)
            traceback.print_exc()
    if args.json is not None:
        import jax
        path = args.json if args.json != "auto" else \
            f"BENCH_{args.only or 'all'}.json"
        with open(path, "w") as f:
            json.dump({"rows": collected, "failed": failed,
                       "quick": bool(args.quick),
                       "backend": jax.default_backend(),
                       "python": platform.python_version(),
                       "meta": bench_meta()}, f, indent=1)
        print(f"# wrote {path} ({len(collected)} rows)", file=sys.stderr)
    if failed:
        raise SystemExit(f"{failed} benchmark modules failed")


if __name__ == "__main__":
    main()
