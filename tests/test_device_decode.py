"""Listed rows decoded on the device against the host's ``decode_batch``.

In sized mode an un-routed batch whose ids fit int32 is decoded on the
device (``listing.decode_on_device``): the host gets compact sorted rows
instead of tile-local emit buffers.  These tests hold it byte-identical to
the host decode, and check where it engages and where the host path stays.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import listing, pipeline
from repro.core.bitops import pack_mask, pack_rows
from repro.core.engine_jax import bucket_rows
from repro.core.engine_np import Stats
from repro.data import rmat_graph
from repro.resilience import inject
from repro.runtime import dispatch as dsp

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "src")


def synthetic_triple(T, l, counts, cap, seed):
    """A batch with random tiles and a kernel-shaped (bufs, cnt, ovf) triple
    bucket-padded like a device harvest; ``counts`` > ``cap`` overflow."""
    rng = np.random.default_rng(seed)
    B = len(counts)
    A, cand, sizes = [], [], []
    for _ in range(B):
        s = int(rng.integers(l + 2, min(T, 24) + 1))
        adj = np.triu(rng.random((s, s)) < 0.7, 1)
        rows = [0] * s
        for u, v in zip(*np.nonzero(adj | adj.T)):
            rows[u] |= 1 << int(v)
        A.append(pack_rows(rows, T))
        cand.append(pack_mask((1 << s) - 1, T))
        sizes.append(s)
    sizes = np.asarray(sizes, dtype=np.int32)
    verts = np.zeros((B, T), dtype=np.int64)
    for b, s in enumerate(sizes):
        verts[b, :s] = rng.choice(2 ** 31 - 1, size=s, replace=False)
    anchors = rng.integers(0, 2 ** 31 - 1, size=(B, 2))
    batch = pipeline.TileBatch(T, np.stack(A), np.stack(cand), sizes,
                               np.ones(B, np.int32), anchors, verts)
    counts = np.asarray(counts, dtype=np.uint32)
    bufs = np.zeros((B, cap, l), dtype=np.int32)
    for b in range(B):
        bufs[b] = rng.integers(0, sizes[b], size=(cap, l))
    ovf = (counts > cap).astype(np.uint32)
    return batch, [bucket_rows(x) for x in (bufs, counts, ovf)]


def _counts(case, cap, rng):
    if case == "overflow":  # two tiles past the buffer, re-listed on the host
        c = rng.integers(0, cap + 1, size=8)
        c[[2, 5]] = cap + 1 + rng.integers(0, 4, size=2)
        return c
    if case == "padded":  # 5 tiles padded to 8 lanes
        return rng.integers(1, cap + 1, size=5)
    if case == "empty":  # R = 0
        return np.zeros(6, dtype=np.int64)
    # R lands exactly on a bucket edge: 5 * 32 rows
    return np.array([cap, cap, 0, cap, cap, cap, 0])


def _check_against_host(T, l, case):
    cap = 32
    counts = _counts(case, cap, np.random.default_rng(T + l))
    batch, (bufs, cnt, ovf) = synthetic_triple(T, l, counts, cap, seed=T * l)
    B = batch.B
    host_stats, dev_stats = Stats(), Stats()
    want = listing.decode_batch(batch, bufs[:B], cnt[:B], ovf[:B], l,
                                host_stats)
    out = tuple(jnp.asarray(x) for x in (bufs, cnt, ovf))
    tables = tuple(jnp.asarray(x) for x in listing.decode_tables(batch))
    dev = listing.decode_on_device(out, tables, l, cnt, cap, B)
    if case == "edge":
        assert dev.expected == 160 == listing.decode_rows_bucket(160)
    if case == "empty":
        assert dev.expected == 0
    got = listing.decode_batch(batch, None, dev.counts, dev.overflow, l,
                               dev_stats,
                               device_rows=listing.fetch_decoded(dev))
    assert got.dtype == want.dtype == np.int64
    assert got.flags.c_contiguous and got.shape == want.shape
    assert got.tobytes() == want.tobytes()
    assert dev_stats.overflowed_tiles == host_stats.overflowed_tiles \
        == int((counts > cap).sum())


_DISPATCH_4 = """
import numpy as np
from repro.core import listing
from repro.data import rmat_graph
import jax
assert jax.device_count() == 4
g = rmat_graph(8, 4, seed=7)
for k in (4, 5):
    runs = []
    for capacity in (None, 1024):  # sized (device decode), pinned (host)
        chunks = []
        sink = listing.CallbackSink(chunks.append)
        res = listing.stream_cliques(g, k, sink, devices=4, batch_size=16,
                                     capacity=capacity, pack_workers=0,
                                     plan_cache=False)
        assert all(c.dtype == np.int64 and c.flags.c_contiguous
                   for c in chunks)
        runs.append((res.stats, [c.tobytes() for c in chunks]))
    (dev, dev_chunks), (host, host_chunks) = runs
    assert dev_chunks == host_chunks and len(dev_chunks) > 1
    assert dev.device_decoded_rows == dev.emitted_cliques > 0
    assert host.device_decoded_rows == 0
    assert len(dev.device_tiles) == 4
print("ok")
"""


@pytest.mark.parametrize(
    "T, l, case",
    [(T, l, case) for T in (32, 64, 128, 256) for l in (1, 2, 3)
     for case in ("overflow", "padded", "empty", "edge")]
    + [(None, None, "dispatcher-4-devices")])
def test_device_decode_is_byte_identical_to_host(T, l, case):
    if case != "dispatcher-4-devices":
        _check_against_host(T, l, case)
        return
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", _DISPATCH_4], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0 and proc.stdout.strip() == "ok", proc.stderr


def test_decode_rows_bucket_ladder():
    assert listing.decode_rows_bucket(0) == listing.DECODE_MIN_ROWS
    prev = 0
    for r in range(1, 20000, 7):
        p = listing.decode_rows_bucket(r)
        assert p >= max(r, listing.DECODE_MIN_ROWS) and p >= prev
        if r > listing.DECODE_MIN_ROWS:
            assert (p - r) * 4 < r  # under a quarter of slack
        prev = p
    ladder = {listing.decode_rows_bucket(r) for r in range(1, 1 << 14)}
    assert len(ladder) == 4 * 7 + 1  # 128 .. 16384, four steps an octave


# ---------------------------------------------------------------------------
# where the device decode engages, and where the host path stays
# ---------------------------------------------------------------------------


K = 5


@pytest.fixture(scope="module")
def graph():
    return rmat_graph(8, 4, seed=7)


@pytest.fixture(scope="module")
def batches(graph):
    return [b for b in pipeline.stream_batches(graph, K, batch_size=16)
            if isinstance(b, pipeline.TileBatch)]


@pytest.fixture(scope="module")
def host_rows(batches):
    """The listing's rows decoded on the host (pinned capacity)."""
    sink = listing.ArraySink(K)
    disp = dsp.ListDispatcher(K - 2, sink=sink, capacity=1024)
    for b in batches:
        disp.submit(b)
    disp.finish()
    assert disp.stats.device_decoded_rows == 0
    return sink.result()


def _dispatch(batches, route=False, **kwargs):
    sink = listing.ArraySink(K)
    stats = Stats()
    disp = dsp.ListDispatcher(K - 2, sink=sink, stats=stats, **kwargs)
    chunks = []

    def to_sink(batch, bufs, cnt, ovf):
        chunks.append(listing.decode_batch(batch, bufs, cnt, ovf, K - 2,
                                           stats))
        return len(chunks[-1])

    try:
        for b in batches:
            disp.submit(b, route=to_sink if route else None)
        disp.finish()
    finally:
        disp.close()
    rows = np.concatenate(chunks) if route else sink.result()
    return rows, stats


@pytest.mark.parametrize("mode", ["sized", "sized-inline", "speculative",
                                  "pinned", "routed", "host-rung"])
def test_device_decoded_rows_counts_where_the_device_decodes(
        monkeypatch, graph, batches, host_rows, mode):
    if mode == "sized-inline":
        sink = listing.ArraySink(K)
        stats = listing.stream_cliques(graph, K, sink, batch_size=16,
                                       plan_cache=False).stats
        rows = sink.result()
    else:
        if mode == "host-rung":  # every list launch fails: host triples
            def refuse(self, *args):
                raise inject.FaultInjected("kernel.launch", 0)
            monkeypatch.setattr(dsp.ListDispatcher, "_list_once", refuse)
        kwargs = {"speculative": dict(capacity="speculative"),
                  "pinned": dict(capacity=8)}.get(mode, {})
        rows, stats = _dispatch(batches, route=mode == "routed", **kwargs)
    assert rows.tobytes() == host_rows.tobytes()
    on_device = mode.startswith("sized")
    assert stats.device_decoded_rows == (len(rows) if on_device else 0)
    if mode == "host-rung":
        assert stats.demotions > 0


def test_ids_past_int32_take_the_host_path(batches, host_rows):
    """A graph with n >= 2**31: its ids do not fit the device decode."""
    shift = 2 ** 31
    wide = [pipeline.TileBatch(b.T, b.A, b.cand, b.sizes, b.nedges,
                               b.anchors + shift, b.verts + shift, b.index)
            for b in batches]
    assert not any(listing.decodes_on_device(b) for b in wide)
    assert all(listing.decodes_on_device(b) for b in batches)
    rows, stats = _dispatch(wide)
    assert stats.device_decoded_rows == 0
    assert np.array_equal(rows, host_rows + shift)


def test_row_total_mismatch_raises(monkeypatch, batches):
    """A count pass that disagrees with the list kernel is caught after the
    fetch: the device's row total differs from the host's."""
    real = dsp.ListDispatcher._count_pass

    def plant(self, A, cand):
        return real(self, A, cand) + jnp.uint32(1)

    monkeypatch.setattr(dsp.ListDispatcher, "_count_pass", plant)
    with pytest.raises(RuntimeError, match="disagree"):
        _dispatch(batches)


@pytest.mark.parametrize("site", ["decode_on_device", "fetch_decoded"])
def test_a_failed_device_decode_falls_back_to_the_triple(
        monkeypatch, batches, host_rows, site):
    """A decode that fails to launch, or whose rows are lost, leaves the
    batch to the host decode of its triple: same rows, one retry."""
    real = getattr(listing, site)
    calls = []

    def flaky(*args):
        calls.append(1)
        if len(calls) == 1:
            raise jax.errors.JaxRuntimeError("INTERNAL: buffer lost")
        return real(*args)

    monkeypatch.setattr(listing, site, flaky)
    rows, stats = _dispatch(batches)
    assert rows.tobytes() == host_rows.tobytes()
    assert stats.retries == 1
    assert 0 < stats.device_decoded_rows < len(rows)
