"""Chip smoke: the paper's DelayedFlights job end to end on one TPU.

One chip (the default) runs the DelayedFlights chain of
``examples/flight_delay_pipeline.py`` — map(identity) -> filter(delay > 15)
-> per-carrier delayed count and delay sum — through ``repro.dsl`` and
``Pipeline.run`` in the ``plain``, ``encrypted`` and ``enclave`` modes, at
the generator's widths (16 u32 words per record, 20 carriers).  Each mode
first streams one window (every program of the job compiles there), then
the whole stream; both results must equal a numpy reference computed
straight from the records.  The script also checks that a ``seal_many``
program and an ``enclave_map_rows`` program hold a compiled TPU kernel
(``tpu_custom_call``), not an interpreted one.

``--chips 4`` runs only the sealed keyed shuffle (``keyed_route`` with an
edge handle -> ``secure_exchange``) on a 4-chip ``model`` mesh, and checks
it against the plain exchange and a host-side bucketing.

    python chip_smoke.py                 # 1 chip, 2^20 records per mode
    python chip_smoke.py --chips 4       # the sealed shuffle on 4 chips

The script exits non-zero, printing no result, unless JAX's first device
is a TPU.  The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

CARRIERS = 20
DELAY_THRESHOLD = 15
RECORDS = 1 << 20          # streamed per mode: 64 windows, 64 MiB
CHUNK = 1024               # records per source chunk
WORKERS = 2                # workers per stage
WINDOW_CHUNKS = 8          # Pipeline's default window factor
SHUFFLE_RECORDS = 8192     # records in each chip's outbox (--chips 4)
MODES = ("plain", "encrypted", "enclave")


# ---------------------------------------------------------------- phases


def reference(records: np.ndarray, num_carriers: int = CARRIERS) -> dict:
    """Plain numpy DelayedFlights: delay > 15, then count and delay sum
    per carrier."""
    from repro.data.synthetic import CARRIER_WORD, DELAY_WORD
    carrier = records[:, CARRIER_WORD].astype(np.int64)
    delay = records[:, DELAY_WORD].astype(np.int64)
    keep = delay > DELAY_THRESHOLD
    total = np.zeros(num_carriers, np.int64)
    np.add.at(total, carrier[keep], delay[keep])
    return {"count": np.bincount(carrier[keep], minlength=num_carriers),
            "sum": total}


def matches(result: dict, ref: dict) -> bool:
    """Exact equality of the per-carrier counts and sums."""
    return all(np.array_equal(np.asarray(result[k]), ref[k])
               for k in ("count", "sum"))


def build_job(mode: str, workers: int):
    """The DelayedFlights chain, compiled to a ``Pipeline``."""
    from repro.dsl import stream
    return (stream()
            .map("identity", name="sgx_mapper", workers=workers, sgx=True)
            .filter("delay_filter_u32", const=DELAY_THRESHOLD,
                    name="sgx_filter", workers=workers, sgx=True)
            .reduce("carrier_delay_stats", name="reducer")
            .window(WINDOW_CHUNKS)
            .build(mode))


def stream_records(mode: str, records: np.ndarray, *, chunk: int,
                   workers: int):
    """One ``Pipeline.run`` over ``records`` in ``chunk``-record source
    chunks -> (per-carrier result, seconds in ``run``)."""
    import jax.numpy as jnp
    pipe = build_job(mode, workers)
    src = (jnp.asarray(records[i:i + chunk])
           for i in range(0, len(records), chunk))
    t0 = time.perf_counter()
    out = pipe.run(src)
    return out, time.perf_counter() - t0


def run_mode(mode: str, records: np.ndarray, *, chunk: int = CHUNK,
             workers: int = WORKERS) -> dict:
    """First window (compiles the job's programs), then the whole stream.
    Both results are checked against :func:`reference`."""
    window = chunk * workers * WINDOW_CHUNKS
    if len(records) % window:
        raise ValueError(f"{len(records)} records are not whole windows "
                         f"of {window}")
    first, first_s = stream_records(mode, records[:window], chunk=chunk,
                                    workers=workers)
    out, steady_s = stream_records(mode, records, chunk=chunk,
                                   workers=workers)
    return {"mode": mode, "records": len(records), "window": window,
            "first_window_s": first_s, "steady_s": steady_s,
            "ok": matches(first, reference(records[:window]))
            and matches(out, reference(records)),
            "result": out}


def kernel_texts(*, batch: int, n_words: int, rows: int) -> dict:
    """Compiled text of one ``seal_many`` program and one
    ``enclave_map_rows`` program at the engine's window shapes."""
    import functools

    import jax
    import jax.numpy as jnp
    from repro.crypto import aead
    from repro.kernels.enclave_map.ops import enclave_map_rows

    def u32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.uint32)

    seal = jax.jit(aead.seal_words).lower(
        u32(8), u32(batch, 3), u32(batch, n_words))
    enclave = jax.jit(functools.partial(
        enclave_map_rows, op="delay_filter_u32",
        const=float(DELAY_THRESHOLD))).lower(
        u32(8), u32(8), u32(rows, 3), u32(rows), u32(rows, 16))
    return {"seal_many": seal.compile().as_text(),
            "enclave_map_rows": enclave.compile().as_text()}


def host_buckets(records: np.ndarray, W: int) -> tuple:
    """Host keyed shuffle: inbox[j][i] = rows of outbox i whose
    carrier % W == j, in stream order; counts[j, i] their number."""
    from repro.data.synthetic import CARRIER_WORD
    inbox = [[r[r[:, CARRIER_WORD] % W == j] for r in records]
             for j in range(W)]
    counts = np.array([[len(b) for b in row] for row in inbox])
    return inbox, counts


def sealed_shuffle(devices, *, n_records: int = SHUFFLE_RECORDS,
                   seed: int) -> dict:
    """The sealed keyed shuffle on a ``("model": W)`` mesh: each device's
    outbox is one DelayedFlights window bucketed by carrier, routed
    through ``keyed_route(..., key=<edge handle>)``, and compared with the
    plain exchange and with :func:`host_buckets`."""
    import re

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.attest.directory import KeyDirectory
    from repro.attest.measure import IO_ENDPOINT
    from repro.data.synthetic import CARRIER_WORD, flight_records
    from repro.dist.collectives import keyed_route
    from repro.launch.mesh import make_mesh

    W = len(devices)
    mesh = make_mesh((W,), ("model",), devices=devices)
    recs = flight_records(W * n_records, seed=seed).reshape(W, n_records, -1)
    rows = NamedSharding(mesh, P("model"))
    x = jax.device_put(recs, rows)
    carriers = jax.device_put(recs[:, :, CARRIER_WORD], rows)
    d = KeyDirectory(seed=seed)
    d.enroll("shuffle/a", IO_ENDPOINT, allow=True)
    d.enroll("shuffle/b", IO_ENDPOINT, allow=True)
    d.establish("shuffle", "shuffle/a", "shuffle/b")
    edge = d.handle("shuffle")

    def route(key=None):
        return keyed_route(x, carriers, mesh, "model", key=key,
                           hash_keys=False)

    t0 = time.perf_counter()
    inbox, counts, ok = jax.block_until_ready(route(edge))
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    inbox, counts, ok = jax.block_until_ready(route(edge))
    steady_s = time.perf_counter() - t0
    plain_inbox, plain_counts, _ = route()

    inbox, counts = np.asarray(inbox), np.asarray(counts)
    want, want_counts = host_buckets(recs, W)
    host_ok = np.array_equal(counts, want_counts) and all(
        np.array_equal(inbox[j, i, :counts[j, i]], want[j][i])
        for j in range(W) for i in range(W))
    text = jax.jit(lambda a, k: keyed_route(
        a, k, mesh, "model", key=edge.key(), step=0,
        hash_keys=False)).lower(x, carriers).compile().as_text()
    return {"W": W, "records_per_device": n_records,
            "first_s": first_s, "steady_s": steady_s,
            "macs_ok": bool(np.asarray(ok).all()),
            "equals_plain": bool(
                np.array_equal(inbox, np.asarray(plain_inbox))
                and np.array_equal(counts, np.asarray(plain_counts))),
            "equals_host": bool(host_ok),
            "hlo": {op: len(re.findall(op, text)) for op in (
                "tpu_custom_call", "all-to-all", "all-gather")}}


# ------------------------------------------------------------------ main


def _fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    raise SystemExit(1)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sealed keyed shuffle on a "
                         "4-chip mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        _fail(f"JAX's first device is {dev.platform!r}, not a TPU")
    t_start = time.perf_counter()
    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro.launch.cache import enable_compile_cache
    cache = enable_compile_cache()
    n_cached = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    print(f"jax {jax.__version__}  device {dev.device_kind} "
          f"x{len(jax.devices())}")
    print(f"compile cache: {cache} ({n_cached} entries at start)")

    if args.chips == 4:
        if len(jax.devices()) < 4:
            _fail(f"--chips 4 needs 4 devices, found {len(jax.devices())}")
        r = sealed_shuffle(jax.devices()[:4], seed=args.seed)
        print(f"sealed shuffle W={r['W']} records/chip="
              f"{r['records_per_device']} first_s={r['first_s']} "
              f"steady_s={r['steady_s']} macs_ok={r['macs_ok']} "
              f"equals_plain={r['equals_plain']} "
              f"equals_host={r['equals_host']} hlo={r['hlo']}")
        if not (r["macs_ok"] and r["equals_plain"] and r["equals_host"]):
            _fail("sealed shuffle disagrees with its references")
        if (not r["hlo"]["tpu_custom_call"] or r["hlo"]["all-to-all"] != 1
                or r["hlo"]["all-gather"]):
            _fail(f"sealed shuffle program is not per-shard: {r['hlo']}")
    else:
        from repro.data.synthetic import flight_records
        from repro.obs.metrics import REGISTRY
        records = flight_records(RECORDS, CARRIERS, seed=args.seed)
        steady = 0.0
        for mode in MODES:
            r = run_mode(mode, records)
            steady += r["steady_s"]
            print(f"mode={mode} records={r['records']} "
                  f"window={r['window']} "
                  f"first_window_s={r['first_window_s']} "
                  f"steady_s={r['steady_s']} "
                  f"records_per_s={r['records'] / r['steady_s']} "
                  f"matches_reference={r['ok']}")
            if not r["ok"]:
                _fail(f"{mode} result differs from the numpy reference")
        print("fast-path compiles: "
              f"{REGISTRY.counter('aead.fastpath.compiles').value}")
        # an ingress window seals workers * WINDOW_CHUNKS chunks; one
        # worker's enclave share is WINDOW_CHUNKS chunks, a record per row
        texts = kernel_texts(batch=WORKERS * WINDOW_CHUNKS,
                             n_words=CHUNK * 16,
                             rows=WINDOW_CHUNKS * CHUNK)
        for name, text in texts.items():
            found = "tpu_custom_call" in text
            print(f"{name} program holds tpu_custom_call: {found}")
            if not found:
                _fail(f"{name} did not compile to a TPU kernel")
        print(f"setup_s={time.perf_counter() - t_start - steady} "
              f"(everything but the steady streams)")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
