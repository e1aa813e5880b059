"""A cell cut to a size the CPU runs in seconds (16-record chunks, Pallas
interpreted), and the faults that the check has to catch."""
import time

from streambench import harness, layout

CHUNK = 16


def cell(workload: str, **traffic) -> layout.Cell:
    c = layout.resolve(layout.load_benchmark(), workload)
    c.config = dict(c.config, chunk_records=CHUNK,
                    pool_records=CHUNK * harness.window_chunks(c.config) * 4)
    c.traffic = dict(c.traffic, **traffic)
    return c


def run(c: layout.Cell, tmp_path, *, seed: int = 2 ** 31 + 7,
        seconds: float = 0.3, trace: bool = False, **kw):
    return harness.run_cell(c, seed, seconds, trace,
                            peaks={"hbm_bytes_per_s": 819e9},
                            t_process=time.perf_counter(),
                            out_dir=str(tmp_path), log=lambda m: None, **kw)


def unchanged_state(monkeypatch):
    """The sink's fold returns its state unchanged."""
    from repro.dsl import reducers
    real = reducers.REDUCERS["carrier_delay_stats"]

    def factory(**kw):
        _, init = real(**kw)
        return (lambda acc, chunk: acc), init
    monkeypatch.setitem(reducers.REDUCERS, "carrier_delay_stats", factory)


def half_batch(monkeypatch):
    """Egress opens only the first half of each window's rows."""
    from repro.core.pipeline import Pipeline
    real = Pipeline._open_egress

    def half(self, parts, mode, key):
        return real(self, [p.select(list(range(len(p) // 2)))
                           for p in parts], mode, key)
    monkeypatch.setattr(Pipeline, "_open_egress", half)


def altered_answer(monkeypatch):
    """Each hop's operator output comes back with one word changed (the
    first record's delay word, before it is sealed again)."""
    from repro.core import enclave
    from repro.kernels.enclave_map import ops
    real_rows, real_words = ops.enclave_map_rows, enclave._apply_static_words

    def rows(*a, **kw):
        out = real_rows(*a, **kw)
        return out.at[0, 1].set(out[0, 1] ^ 1)

    def words(*a, **kw):
        out = real_words(*a, **kw)
        return out.at[0, 1].set(out[0, 1] ^ 1)
    monkeypatch.setattr(ops, "enclave_map_rows", rows)
    monkeypatch.setattr(enclave, "_apply_static_words", words)


FAULTS = {"unchanged_state": unchanged_state, "half_batch": half_batch,
          "altered_answer": altered_answer}
