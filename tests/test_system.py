"""End-to-end behaviour tests for the paper's system."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import ebbkc, engine_jax, vbbkc
from repro.data import planted_cliques, powerlaw_graph
from repro.runtime.clique_scheduler import schedule_tiles


def test_end_to_end_planted_clique_recovery():
    """Full pipeline (truss order -> tiles -> device engine) recovers the
    planted structure; all backends and the baseline agree."""
    g = planted_cliques(300, 4, 10, p_noise=0.005, seed=9)
    for k in (4, 6, 8):
        host = ebbkc.count(g, k, order="hybrid", et_t=3)
        dev = ebbkc.count(g, k, backend="jax",
                          engine_kwargs={"interpret": True})
        base = vbbkc.count(g, k, variant="ddegcol+")
        assert host.count == dev.count == base.count
        if k == 8:
            assert host.count >= 4 * 45  # C(10,8)=45 per planted clique


def test_distributed_schedule_then_count():
    """EP scheduling (Section 6.2(7)) partitions tiles; per-bin counting
    sums to the global answer (the multi-device reduction is a psum of
    exactly these partials)."""
    g = powerlaw_graph(800, 10, seed=4)
    k = 5
    binned = engine_jax.bin_tiles(g, k)
    total = 0
    for T, packed in binned.items():
        class _T:
            def __init__(self, s, e):
                self.s, self.nedges = s, e
        metas = [_T(T, T * 2) for _ in range(packed.A.shape[0])]
        device_bins, stats = schedule_tiles(metas, k - 2, n_devices=4)
        assert stats["max_over_mean"] < 1.5
        for bin_ids in device_bins:
            if not bin_ids:
                continue
            idx = np.asarray(bin_ids)
            hard, nv, t, f = engine_jax.count_packed(
                jnp.asarray(packed.A[idx]), jnp.asarray(packed.cand[idx]),
                k - 2, et=True, interpret=True)
            total += engine_jax.combine_counts(hard, nv, t, f, k - 2, True)
    assert total == ebbkc.count(g, k).count


def test_listing_service_bounded_output():
    g = planted_cliques(120, 3, 8, p_noise=0.01, seed=5)
    out, _ = ebbkc.list_cliques(g, 4, max_out=50)
    assert out.shape[1] == 4
    assert len(out) >= 50  # buffer filled
    # all outputs are real cliques
    adj = [set(g.neighbors(v).tolist()) for v in range(g.n)]
    from itertools import combinations
    for row in out[:50].tolist():
        for a, b in combinations(row, 2):
            assert b in adj[a]


@pytest.mark.parametrize("mode", [[], ["--list"]])
def test_launcher_verify_fails_on_mismatch(monkeypatch, capsys, mode):
    """``--verify`` prints the comparison and exits non-zero on a
    mismatch, so a wrong device answer cannot end a run with status 0."""
    from repro.launch import clique

    argv = ["clique", "--graph", "er:60,0.3", "--k", "4", "--devices", "1",
            "--verify"] + mode
    monkeypatch.setattr("sys.argv", argv)
    clique.main()  # honest host count: matches, returns normally
    assert "match=True" in capsys.readouterr().out
    real = ebbkc.count

    def off_by_one(*args, **kwargs):
        res = real(*args, **kwargs)
        res.count += 1
        return res

    monkeypatch.setattr(clique.ebbkc, "count", off_by_one)
    with pytest.raises(SystemExit, match="verify failed"):
        clique.main()
    assert "match=False" in capsys.readouterr().out
