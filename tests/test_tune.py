"""Persistent autotuner (repro.tune): records, cache, search, wiring.

Covers the PR-6 contracts: corrupt / stale-format records read as absent
(never crash), the ``REPRO_BACKEND`` env beats a persisted record, a
persisted record beats a live microbenchmark (warm processes never
re-measure / re-search), concurrent same-directory writers stay atomic,
geometry resolution follows the arg > record > default ladder, and the
batch-shape bucketing that lets padded lanes reuse executables is
result-neutral.
"""
import dataclasses
import json
import os
import threading

import numpy as np
import pytest

from repro import tune
from repro.core.bitops import pack_bits
from repro.kernels import ops
from repro.tune import cache as tcache
from repro.tune import search as tsearch
from repro.tune.records import TuningRecord


@pytest.fixture
def tune_dir(tmp_path, monkeypatch):
    """Isolated tuning cache: fresh dir, no env leakage, clean globals."""
    monkeypatch.delenv(tcache.ENV_TUNE_CACHE, raising=False)
    monkeypatch.delenv(ops.BACKEND_ENV, raising=False)
    d = str(tmp_path / "tc")
    tune.configure(d, xla_cache=False)  # never mutate global jax config
    tune.clear_memory()
    tune.consume_events()
    ops.clear_autotune_cache()
    yield d
    tune.configure(None)
    tune.clear_memory()
    tune.consume_events()
    ops.clear_autotune_cache()


def _backend_rec(winner="pallas", mode="count", l=2, T=32, capacity=None):
    return TuningRecord(
        "backend", tune.device_kind(), tune.jax_version(), mode, l,
        T=T, W=T // 32, cap_bucket=tune.capacity_bucket(capacity),
        data={"winner": winner})


def _record_meta_path(tune_dir):
    [digest] = os.listdir(os.path.join(tune_dir, "records"))
    return os.path.join(tune_dir, "records", digest,
                        "step_0000000000", "meta.json")


def test_record_roundtrip_across_processes(tune_dir):
    rec = _backend_rec("lax")
    tune.put(rec)
    tune.clear_memory()  # "new process": only the directory survives
    got = tune.get(rec.key())
    assert got is not None
    assert got.data["winner"] == "lax"
    assert got.key() == rec.key()


def test_corrupt_record_reads_as_absent(tune_dir):
    rec = _backend_rec("lax")
    tune.put(rec)
    with open(_record_meta_path(tune_dir), "w") as f:
        f.write("{ not json")
    tune.clear_memory()
    assert tune.get(rec.key()) is None
    # ...and backend="autotune" falls back to a live measurement, no crash
    assert ops.autotune_backend("count", 2, 32) in ("lax", "pallas")


def test_stale_format_record_reads_as_absent(tune_dir):
    rec = _backend_rec("pallas")
    tune.put(rec)
    p = _record_meta_path(tune_dir)
    with open(p) as f:
        meta = json.load(f)
    meta["metadata"]["format"] = 0  # a pre-PR6 layout
    with open(p, "w") as f:
        json.dump(meta, f)
    tune.clear_memory()
    assert tune.get(rec.key()) is None


def test_env_backend_overrides_persisted_record(tune_dir, monkeypatch):
    tune.put(_backend_rec("pallas"))
    monkeypatch.setenv(ops.BACKEND_ENV, "lax")
    assert ops.autotune_backend("count", 2, 32) == "lax"
    # the env short-circuit consults no cache layer: no events at all
    assert tune.consume_events() == (0.0, 0, 0)


def test_persisted_record_skips_microbench(tune_dir, monkeypatch):
    tune.put(_backend_rec("lax"))
    monkeypatch.setattr(
        tsearch, "microbench_backend",
        lambda *a, **k: pytest.fail("microbenchmark re-ran on a warm key"))
    assert ops.autotune_backend("count", 2, 32) == "lax"
    tune_s, lookups, misses = tune.consume_events()
    assert lookups == 1 and misses == 0
    # second call answers from the in-process layer, same verdict
    assert ops.autotune_backend("count", 2, 32) == "lax"
    _, lookups, misses = tune.consume_events()
    assert lookups == 1 and misses == 0


def test_concurrent_writers_stay_atomic(tune_dir):
    """Same-key writers race benignly: no exceptions escape, and a reader
    only ever sees a fully committed record (or none), never garbage."""
    rec = _backend_rec("lax")
    errors = []

    def write(winner):
        try:
            for _ in range(5):
                tune.put(_backend_rec(winner))
        except Exception as e:  # pragma: no cover - the bug being tested
            errors.append(e)

    threads = [threading.Thread(target=write, args=(w,))
               for w in ("lax", "pallas") * 3]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    tune.clear_memory()
    got = tune.get(rec.key())
    assert got is None or got.data["winner"] in ("lax", "pallas")
    # the cache recovers: one clean write-after-the-race round-trips
    tune.put(_backend_rec("lax"))
    tune.clear_memory()
    assert tune.get(rec.key()).data["winner"] == "lax"


def test_geometry_precedence_ladder(tune_dir):
    # no record anywhere: the hardcoded defaults
    g0 = tsearch.resolve_geometry("list", 3)
    assert (g0.batch_size, g0.t_policy, g0.cap_policy) == \
        (256, "pow2", "pow2")
    assert tune.consume_events() == (0.0, 0, 0)  # untuned != cache miss
    # a persisted record becomes the default...
    tune.put(TuningRecord(
        "geometry", tune.device_kind(), tune.jax_version(), "list", 3,
        data={"batch_size": 64, "t_policy": "mult32",
              "cap_policy": "mult64"}))
    tune.clear_memory()
    g1 = tsearch.resolve_geometry("list", 3)
    assert g1.batch_size == 64
    assert g1.bins == tsearch.bins_for("mult32")
    assert g1.cap_policy == "mult64"
    _, lookups, misses = tune.consume_events()
    assert lookups == 1 and misses == 0
    # ...but an explicit argument still wins, per knob
    g2 = tsearch.resolve_geometry("list", 3, batch_size=128,
                                  cap_policy="pow2")
    assert g2.batch_size == 128
    assert g2.cap_policy == "pow2"
    assert g2.bins == tsearch.bins_for("mult32")  # inherited from record
    # an explicit ladder wins even when it matches no named policy
    # (bins=(32,) is how the spill tests force oversize tiles)
    g3 = tsearch.resolve_geometry("list", 3, bins=(32,))
    assert g3.bins == (32,)
    g4 = tsearch.resolve_geometry("list", 3, bins=(32, 64, 128, 256))
    assert g4.bins == tsearch.bins_for("pow2")
    assert g4.t_policy == "pow2"  # a policy-shaped ladder maps back


def test_warm_process_reuses_tuned_geometry_without_search(tune_dir,
                                                          monkeypatch):
    from repro.data import rmat_graph

    rec = tsearch.tune_geometry("count", 1, budget_s=2.0,
                                graph=rmat_graph(7, 6, seed=1))
    tuned = tsearch.geometry_from_record(rec)
    # "second process": in-memory layers gone, only the record dir remains
    tune.clear_memory()
    ops.clear_autotune_cache()
    monkeypatch.setattr(
        tsearch, "_eval_geometry",
        lambda *a, **k: pytest.fail("geometry re-searched on a warm key"))
    got = tsearch.resolve_geometry("count", 1)
    assert dataclasses.asdict(got) == dataclasses.asdict(tuned)
    assert rec.data["searched"] and rec.data["evals"] >= 1


def _packed(rng, B, T):
    dense = rng.random((B, T, T)) < 0.4
    dense = np.triu(dense, 1)
    dense = dense | dense.transpose(0, 2, 1)
    return pack_bits(dense), pack_bits(np.ones((B, T), bool))


def test_bucket_rows_padding_is_neutral():
    """Batch-shape bucketing pads to the next pow2 with zero-cand lanes so
    padded batches reuse executables; the pads must contribute nothing."""
    from repro.core.engine_jax import bucket_rows

    rng = np.random.default_rng(5)
    A, cand = _packed(rng, 5, 32)
    Ab, cb = bucket_rows(A), bucket_rows(cand)
    assert Ab.shape[0] == 8 and cb.shape[0] == 8
    assert (Ab[5:] == 0).all() and (cb[5:] == 0).all()
    assert bucket_rows(Ab) is Ab  # already a pow2: no copy
    base = np.asarray(ops.count_tiles(A, cand, 2, backend="lax"))
    padded = np.asarray(ops.count_tiles(Ab, cb, 2, backend="lax"))
    np.testing.assert_array_equal(padded[:5], base)
    assert (padded[5:] == 0).all()
    buf, cnts, ovf = (np.asarray(x) for x in
                      ops.list_tiles(Ab, cb, 2, capacity=64, backend="lax"))
    np.testing.assert_array_equal(cnts[:5], base)
    assert (cnts[5:] == 0).all() and not ovf[5:].any()


def test_drain_tune_events_never_clobbers_verdict(tune_dir):
    from repro.core.engine_np import Stats

    st = Stats()
    tune.note_event(seconds=0.5, lookup=True, miss=True)
    ops.drain_tune_events(st)
    assert st.tune_s == 0.5 and st.tune_cache_hit is False
    tune.note_event(lookup=True)
    ops.drain_tune_events(st)
    assert st.tune_cache_hit is True
    # an empty drain (engines and dispatchers share one Stats and both
    # drain) must leave the verdict and the seconds untouched
    ops.drain_tune_events(st)
    assert st.tune_cache_hit is True and st.tune_s == 0.5


@pytest.fixture
def jax_cache_config():
    """Restore the global compilation-cache config after a test moves it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_entry_size_bytes",
             "jax_persistent_cache_min_compile_time_secs")
    prev = {n: getattr(jax.config, n) for n in names}
    yield jax.config
    for n, v in prev.items():
        jax.config.update(n, v)
    cc.reset_cache()


def test_compile_cache_honours_env_dir(tmp_path, monkeypatch,
                                       jax_cache_config):
    d = str(tmp_path / "from_env")
    monkeypatch.setenv(tcache.ENV_COMPILE_CACHE, d)
    assert tune.enable_compilation_cache() == d
    assert jax_cache_config.jax_compilation_cache_dir == d
    assert jax_cache_config.jax_persistent_cache_min_compile_time_secs == 0.0
    assert os.path.isdir(d)
    # a tune-cache directory no longer redirects the compile cache
    tune.configure(str(tmp_path / "tc"))
    try:
        assert jax_cache_config.jax_compilation_cache_dir == d
    finally:
        tune.configure(None)


def test_compile_cache_defaults_to_fixed_repo_path(monkeypatch,
                                                   jax_cache_config):
    monkeypatch.delenv(tcache.ENV_COMPILE_CACHE, raising=False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(root, ".jax_cache")
    assert tcache.REPO_COMPILE_CACHE == want
    assert tune.enable_compilation_cache() == want
    assert jax_cache_config.jax_compilation_cache_dir == want
    with open(os.path.join(root, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_autotune_times_only_compiling_backends(monkeypatch):
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    winner, times = tsearch.microbench_backend("count", 3, 32, trials=1)
    assert winner == "lax" and set(times) == {"lax"}
