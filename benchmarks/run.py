"""Benchmark harness -- one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Host-engine timings run the
paper-faithful bitset recursions (python-int bitsets ~ the paper's bitmap
adjacency); relative comparisons between algorithms reproduce the paper's
figures.  The device-engine roofline projection uses the TPU v5e model of
EXPERIMENTS.md.

    PYTHONPATH=src python -m benchmarks.run [bench_name ...]

Multi-device dispatch sweep (front-end-to-finish wall clock per device
count, parity-checked -- exits non-zero if any device count disagrees):

    PYTHONPATH=src python -m benchmarks.run --devices 1,4 \\
        --graph rmat:12 --k 5 --json BENCH.json

The sweep forges virtual CPU devices itself when XLA_FLAGS is unset.

Autotuner integration: ``--tune-cache DIR`` activates the persistent
tuning cache (repro.tune) for the whole run; ``--phase cold|warm`` tags
every record so a second process sharing the cache can ``--append`` its
records to the same JSON and ``--assert-warm FACTOR`` that its summed
``tune_s + kernel_compile_s`` is at least FACTOR x cheaper than the cold
phase's (the CI warm-start gate).  ``--tune`` runs the budgeted geometry
search and records tuned-vs-default listing rows side by side.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

from .common import emit, graph_suite, timed

from repro.core import ebbkc, vbbkc
from repro.core.graph import degeneracy_order, max_clique_size
from repro.core.truss import truss_decomposition


# ---------------------------------------------------------------------------
# Table 1: dataset statistics (validates tau < delta, the Lemma 4.1 claim)
# ---------------------------------------------------------------------------

def bench_dataset_stats():
    for name, g in graph_suite().items():
        td, dt_t = timed(truss_decomposition, g)
        (_, delta), _ = timed(degeneracy_order, g)
        omega = max_clique_size(g)
        deg = g.degrees().max() if g.n else 0
        assert td.tau < delta, f"Lemma 4.1 violated on {name}"
        emit(f"stats/{name}", dt_t,
             f"n={g.n};m={g.m};maxdeg={deg};delta={delta};tau={td.tau};"
             f"omega={omega};tau_lt_delta=True")


# ---------------------------------------------------------------------------
# Fig 4/5: runtime vs k -- EBBkC+ET against the VBBkC baselines
# ---------------------------------------------------------------------------

def bench_kclique_runtime():
    for name, g in graph_suite().items():
        for k in (4, 5, 6, 7, 8):
            r_e, t_e = timed(ebbkc.count, g, k, order="hybrid", et_t=3)
            r_v, t_v = timed(vbbkc.count, g, k, variant="ddegcol")
            r_d, t_d = timed(vbbkc.count, g, k, variant="degen")
            assert r_e.count == r_v.count == r_d.count
            emit(f"runtime/{name}/k{k}/ebbkc+et", t_e,
                 f"count={r_e.count};speedup_vs_ddegcol={t_v / t_e:.2f};"
                 f"speedup_vs_degen={t_d / t_e:.2f}")
            emit(f"runtime/{name}/k{k}/ddegcol", t_v, f"count={r_v.count}")
            emit(f"runtime/{name}/k{k}/degen", t_d, f"count={r_d.count}")


# ---------------------------------------------------------------------------
# Fig 6: ablation -- framework vs early-termination contributions
# ---------------------------------------------------------------------------

def bench_ablation():
    for name in ("ba3k", "plant"):
        g = graph_suite()[name]
        for k in (5, 7):
            r1, t1 = timed(ebbkc.count, g, k, order="hybrid", et_t=3)
            r2, t2 = timed(ebbkc.count, g, k, order="hybrid", et_t=0)
            r3, t3 = timed(vbbkc.count, g, k, variant="ddegcol+")
            r4, t4 = timed(vbbkc.count, g, k, variant="ddegcol")
            assert r1.count == r2.count == r3.count == r4.count
            emit(f"ablation/{name}/k{k}/ebbkc+et", t1,
                 f"branches={r1.stats.branches};et_hits={r1.stats.et_hits}")
            emit(f"ablation/{name}/k{k}/ebbkc", t2,
                 f"branches={r2.stats.branches}")
            emit(f"ablation/{name}/k{k}/ddegcol+rule2", t3,
                 f"branches={r3.stats.branches}")
            emit(f"ablation/{name}/k{k}/ddegcol", t4,
                 f"branches={r4.stats.branches}")


# ---------------------------------------------------------------------------
# Table 2: ordering generation time (truss vs degeneracy)
# ---------------------------------------------------------------------------

def bench_ordering_time():
    for name, g in graph_suite().items():
        _, t_truss = timed(truss_decomposition, g)
        _, t_degen = timed(degeneracy_order, g)
        emit(f"ordering/{name}/truss", t_truss,
             f"ratio_vs_degen={t_truss / max(t_degen, 1e-9):.2f}")
        emit(f"ordering/{name}/degeneracy", t_degen, "")


# ---------------------------------------------------------------------------
# Fig 7: the three edge orderings (T / C / H), all with ET
# ---------------------------------------------------------------------------

def bench_edge_orderings():
    for name in ("ba3k", "er1k"):
        g = graph_suite()[name]
        for k in (5, 6):
            res = {}
            for order in ("truss", "color", "hybrid"):
                r, t = timed(ebbkc.count, g, k, order=order, et_t=3)
                res[order] = (r, t)
            counts = {r.count for r, _ in res.values()}
            assert len(counts) == 1
            for order, (r, t) in res.items():
                emit(f"edge_order/{name}/k{k}/{order}", t,
                     f"branches={r.stats.branches};"
                     f"max_tile={r.max_tile}")


# ---------------------------------------------------------------------------
# Fig 8: effect of the new color Rule (2)
# ---------------------------------------------------------------------------

def bench_rule2():
    for name in ("ba3k", "rmat12"):
        g = graph_suite()[name]
        for k in (5, 7, 9):
            r2, t2 = timed(ebbkc.count, g, k, order="hybrid", et_t=3,
                           use_rule2=True)
            r0, t0 = timed(ebbkc.count, g, k, order="hybrid", et_t=3,
                           use_rule2=False)
            assert r2.count == r0.count
            emit(f"rule2/{name}/k{k}/with", t2,
                 f"pruned={r2.stats.pruned_color}")
            emit(f"rule2/{name}/k{k}/without", t0,
                 f"pruned={r0.stats.pruned_color};"
                 f"speedup={t0 / max(t2, 1e-9):.2f}")


# ---------------------------------------------------------------------------
# Fig 9: early-termination threshold t
# ---------------------------------------------------------------------------

def bench_et_t():
    g = graph_suite()["plant"]
    for k in (6, 9):
        base = None
        for t_plex in (0, 2, 3, 4, 5):
            r, t = timed(ebbkc.count, g, k, order="hybrid", et_t=t_plex)
            if base is None:
                base = r.count
            assert r.count == base
            emit(f"et_t/plant/k{k}/t{t_plex}", t,
                 f"et_hits={r.stats.et_hits};branches={r.stats.branches}")


# ---------------------------------------------------------------------------
# Fig 10: parallelization -- NP vs EP vs LPT-scheduled EP load balance
# ---------------------------------------------------------------------------

def bench_parallel():
    from repro.core import pipeline
    from repro.core import tiles as tiles_mod
    from repro.core.engine_np import Stats, count_rec_C, count_rec_V
    from repro.runtime.clique_scheduler import balanced_bins

    g = graph_suite()["ba3k"]
    k = 6
    # true per-unit work = measured branch count per top-level branch
    ep_costs = []
    for tile in pipeline.iter_tiles(g, k, mode="hybrid"):
        st = Stats()
        count_rec_C(tile.rows, (1 << tile.s) - 1, k - 2, st,
                    colors=tile.colors, et_t=3)
        ep_costs.append(st.branches + tile.s + 1)
    np_costs = []
    for tile in tiles_mod.vertex_tiles(g, k, colored=True):
        st = Stats()
        count_rec_V(tile.rows, (1 << tile.s) - 1, k - 1, st,
                    colors=tile.colors, et_t=3)
        np_costs.append(st.branches + tile.s + 1)
    for n_dev in (16, 64, 256):
        for scheme, costs in (("np", np_costs), ("ep", ep_costs)):
            # round-robin static assignment (the naive scheme)
            loads = np.zeros(n_dev)
            for i, c in enumerate(costs):
                loads[i % n_dev] += c
            rr = loads.max() / max(loads.mean(), 1e-9)
            _, lpt_loads = balanced_bins(costs, n_dev)
            lpt = lpt_loads.max() / max(lpt_loads.mean(), 1e-9)
            emit(f"parallel/ba3k/k{k}/{scheme}/dev{n_dev}", 0.0,
                 f"units={len(costs)};roundrobin_imbalance={rr:.3f};"
                 f"lpt_imbalance={lpt:.3f};"
                 f"parallel_efficiency={1 / lpt:.3f}")


# ---------------------------------------------------------------------------
# Pipeline front-end: reference vs vectorized extraction + stage breakdown
# ---------------------------------------------------------------------------

def bench_pipeline_stages():
    """Front-end comparison + per-stage timing on rmat12/k=5.

    "reference" is the pre-pipeline front-end (pure-Python extractor in
    ``core.tiles`` + per-bit packer), kept as the parity oracle;
    "vectorized" is ``core.pipeline``.  The e2e rows break the accelerator
    engine's wall-clock into extract / pack / device / combine stages and
    derive the end-to-end speedup of swapping front-ends under the same
    device compute.
    """
    from repro.core import engine_jax, packing, pipeline
    from repro.core import tiles as tiles_mod

    g = graph_suite()["rmat12"]
    k = 5

    def reference_frontend():
        binned = {}
        for t in tiles_mod.edge_tiles(g, k, mode="hybrid"):
            T = next(b for b in pipeline.BINS if t.s <= b)
            binned.setdefault(T, []).append(t)
        return {T: engine_jax.pack_tiles(ts, T)
                for T, ts in sorted(binned.items())}

    ref, t_ref = timed(reference_frontend)
    batches, t_vec = timed(
        lambda: [b for b in pipeline.stream_batches(g, k, order="hybrid")])
    n_ref = sum(p.A.shape[0] for p in ref.values())
    n_vec = sum(b.B for b in batches if isinstance(b, pipeline.TileBatch))
    assert n_ref == n_vec, (n_ref, n_vec)
    emit("pipeline/rmat12/k5/frontend_reference", t_ref, f"tiles={n_ref}")
    emit("pipeline/rmat12/k5/frontend_vectorized", t_vec,
         f"tiles={n_vec};extract_speedup={t_ref / max(t_vec, 1e-9):.2f}")
    # parallel pack producer: same byte-identical stream, wall clock of
    # draining it with a free consumer (packing overlaps across worker
    # processes)
    workers = packing.default_proc_workers()
    _, t_par = timed(
        lambda: [b for b in pipeline.stream_batches(
            g, k, order="hybrid", pack_workers=None)], repeat=2)
    emit("pipeline/rmat12/k5/frontend_parallel", t_par,
         f"tiles={n_vec};pack_workers={workers};"
         f"speedup_vs_serial={t_vec / max(t_par, 1e-9):.2f}")

    # serial packer + no plan cache: the seed-equivalent arithmetic below
    # subtracts stage seconds from wall-clock, so "pack" must be wall time
    # (parallel workers bill CPU-seconds) and the table build must stay in
    # the "extract" stage (the plan cache would move it to plan_build_s)
    stage = {}
    r, t_e2e = timed(engine_jax.count, g, k,
                     pack_workers=0, plan_cache=False,
                     stage_times=stage)
    breakdown = ";".join(
        f"{s}={stage.get(s, 0.0) * 1e6:.0f}us"
        for s in ("extract", "pack", "device", "combine"))
    emit(f"pipeline/rmat12/k{k}/e2e", t_e2e,
         f"count={r.count};tiles={r.tiles};{breakdown}")
    # seed-equivalent e2e: same device/combine stages, reference front-end
    t_front = stage.get("extract", 0.0) + stage.get("pack", 0.0)
    t_seed = t_e2e - t_front + t_ref
    emit(f"pipeline/rmat12/k{k}/e2e_seed_equiv", t_seed,
         f"frontend={t_ref * 1e6:.0f}us;"
         f"e2e_speedup={t_seed / max(t_e2e, 1e-9):.2f}")

    # emission subsystem on the same graph/k: stage breakdown + throughput
    stage_l = {}
    (_, lst), t_list = timed(
        lambda: ebbkc.list_cliques(
            g, k, backend="jax",
            engine_kwargs=dict(devices=1, stage_times=stage_l)))
    breakdown_l = ";".join(
        f"{s}={stage_l.get(s, 0.0) * 1e6:.0f}us"
        for s in ("extract", "pack", "device", "emit"))
    front_l = stage_l.get("extract", 0.0) + stage_l.get("pack", 0.0)
    emit(f"pipeline/rmat12/k{k}/listing_e2e", t_list,
         f"emitted={lst.emitted_cliques};"
         f"cliques_per_s={lst.emitted_cliques / max(t_list, 1e-9):.0f};"
         f"frontend_s={front_l:.3f};pack_workers={lst.pack_workers};"
         f"overflowed={lst.overflowed_tiles};"
         f"sink_bytes={lst.sink_bytes};{breakdown_l}")


# ---------------------------------------------------------------------------
# Multi-device dispatch: front-end-to-finish sweep over device counts
# ---------------------------------------------------------------------------

def bench_dispatch(graph_spec="rmat:12", ks=(5,), device_counts=None,
                   out_json=None, with_listing=False, baseline=None,
                   backends=("auto",), batch_size=256, phase=None,
                   append=False, assert_warm=None, extra_records=None):
    """Sweep `engine_jax.count(devices=n)` over device counts x backends.

    Times front-end-to-finish (extract + pack + device + combine, plan
    prebuilt) per (backend, device count) with double-buffered staging,
    emits the speedup vs the 1-device baseline, and verifies every cell
    produces the identical clique count -- any mismatch exits non-zero
    (the CI bench-smoke gate).

    With ``with_listing`` the sweep also runs the emission subsystem per
    (k, devices, backend): end-to-end listing throughput in cliques/s
    PLUS a kernel-stage-only row (``kernel_seconds`` = device wall time
    from ``stage_times``, i.e. excluding extract/pack/decode), so device
    time is attributable separately from staging.  ``baseline`` (a
    previously committed JSON, e.g. BENCH_pr4.json) diffs every matching
    record's count/emitted against this run -- a count regression fails
    loudly (non-zero exit).

    Every record carries the autotuner columns (``tune_s``,
    ``tune_cache_hit``, ``kernel_compile_s``) plus the roofline inputs
    (``device_flops``, ``device_bytes``).  ``phase`` tags the records
    (cold/warm); ``append`` merges them into an existing ``out_json``;
    ``assert_warm`` enforces the warm-start contract across the two
    phases (see :func:`assert_warm_start`).
    """
    import jax
    from repro.core import ebbkc, engine_jax, pipeline
    from repro.launch.clique import load_graph
    from repro.runtime.dispatch import resolve_devices

    import time as _time

    import jax.numpy as jnp
    import numpy as _np
    from repro.core import listing as listing_mod
    from repro.core import tiles as tiles_mod
    from repro.kernels import ops as kops

    counts = sorted(set(device_counts or {1, jax.device_count()}))
    if counts[0] != 1:
        counts = [1] + counts
    g = load_graph(graph_spec)
    # CSV-safe name: er:400,0.06 -> er400-0.06
    gname = graph_spec.replace(":", "").replace(",", "-")
    plan = pipeline.build_plan(g, order="hybrid")
    records = []
    mismatches = []

    def kernel_stage_listing(k, backend):
        """Pure device-stage listing throughput: pre-staged arrays, warmed
        jit caches, time ONLY the listing-kernel calls (the count pass
        that sizes the buffers is reported separately as sizing_s) --
        device time attributable apart from extract/pack/decode."""
        l = k - 2
        staged = []
        for item in pipeline.stream_batches(plan, k, order="hybrid",
                                            batch_size=batch_size):
            if isinstance(item, tiles_mod.Tile):
                continue  # oversize spills are host work, not kernel stage
            staged.append((jnp.asarray(item.A), jnp.asarray(item.cand)))
        t0 = _time.perf_counter()
        caps = []
        emitted = 0
        for A, cand in staged:
            cnt = _np.asarray(kops.count_tiles(A, cand, l, backend=backend))
            caps.append(listing_mod.capacity_for(cnt, listing_mod.MAX_CAPACITY))
            emitted += int(cnt.sum())
        sizing_s = _time.perf_counter() - t0
        for (A, cand), cap in zip(staged, caps):  # warmup: compile all sigs
            jax.block_until_ready(
                kops.list_tiles(A, cand, l, capacity=cap, backend=backend))
        kernel_s = float("inf")
        for _ in range(2):  # best of 2 (shared CI/container noise)
            t0 = _time.perf_counter()
            outs = [
                kops.list_tiles(A, cand, l, capacity=cap, backend=backend)
                for (A, cand), cap in zip(staged, caps)
            ]
            jax.block_until_ready(outs)
            kernel_s = min(kernel_s, _time.perf_counter() - t0)
        # drain first-call compile seconds accrued by the eager kernel
        # calls above so they are not misattributed to the next engine
        # record's kernel_compile_s
        kops.consume_compile_s()
        return emitted, kernel_s, sizing_s, len(staged)
    for k in ks:
        ref_count = None
        for backend in backends:
            base_t = None
            for n in counts:
                used = len(resolve_devices(n))
                # cold pass carries whatever compile this cell actually
                # pays (first-call signatures are process-wide, so later
                # cells legitimately report ~0); warm pass gives the
                # steady-state stage breakdown, timing is best of the two
                r_cold, t_cold = timed(engine_jax.count, g, k, plan=plan,
                                       devices=n, backend=backend,
                                       batch_size=batch_size)
                stage = {}
                r, t_warm = timed(engine_jax.count, g, k, plan=plan,
                                  devices=n, backend=backend,
                                  batch_size=batch_size,
                                  stage_times=stage)
                compile_s = (r_cold.stats.kernel_compile_s
                             + r.stats.kernel_compile_s)
                # tune events: sum the seconds over both passes, but the
                # hit verdict is the COLD pass's (the first resolution in
                # this process -- the warm pass always hits in-process)
                tune_s = r_cold.stats.tune_s + r.stats.tune_s
                tune_hit = r_cold.stats.tune_cache_hit
                t = min(t_cold, t_warm)
                if base_t is None:
                    base_t = t
                if ref_count is None:
                    ref_count = r.count
                elif r.count != ref_count:
                    mismatches.append((k, n, r.count, ref_count))
                speedup = base_t / max(t, 1e-9)
                dev_s = stage.get("device", 0.0)
                front_s = stage.get("extract", 0.0) + stage.get("pack", 0.0)
                emit(f"dispatch/{gname}/k{k}/{backend}/dev{n}", t,
                     f"count={r.count};tiles={r.tiles};devices_used={used};"
                     f"kernel_s={dev_s:.3f};frontend_s={front_s:.3f};"
                     f"overlap_s={r.stats.staging_overlap_s:.3f};"
                     f"compile_s={compile_s:.3f};"
                     f"tune_s={tune_s:.3f};tune_hit={tune_hit};"
                     f"pack_workers={r.stats.pack_workers};"
                     f"speedup_vs_dev1={speedup:.2f}")
                records.append({
                    "kind": "count", "backend": backend,
                    "graph": graph_spec, "k": k, "devices": n,
                    "devices_used": used, "seconds": t, "count": r.count,
                    "kernel_seconds": dev_s,
                    "frontend_s": front_s,
                    "pack_workers": r.stats.pack_workers,
                    "tiles": r.tiles, "spilled": r.stats.spilled_tiles,
                    "staging_overlap_s": r.stats.staging_overlap_s,
                    "kernel_compile_s": compile_s,
                    "tune_s": tune_s,
                    "tune_cache_hit": tune_hit,
                    "device_flops": sum(r.stats.device_flops.values()),
                    "device_bytes": sum(r.stats.device_bytes.values()),
                    "phase": phase,
                    "speedup_vs_dev1": speedup,
                })
                if not with_listing:
                    continue
                stage_l = {}
                lst_runs = []

                def run_listing():
                    out = ebbkc.list_cliques(
                        g, k, backend="jax", plan=plan,
                        engine_kwargs=dict(devices=n, backend=backend,
                                           batch_size=batch_size,
                                           stage_times=stage_l))
                    lst_runs.append(out[1])
                    return out
                # best of 2 like the count sweep: the serving model pays
                # kernel compiles once per process, not per query
                (_, lst), t_l = timed(run_listing, repeat=2)
                # like the count rows: seconds sum over the repeats, the
                # hit verdict is the first repeat's (process-cold)
                tune_s_l = sum(s.tune_s for s in lst_runs)
                tune_hit_l = lst_runs[0].tune_cache_hit
                compile_l = sum(s.kernel_compile_s for s in lst_runs)
                if lst.emitted_cliques != ref_count:
                    mismatches.append((k, n, lst.emitted_cliques, ref_count))
                rate = lst.emitted_cliques / max(t_l, 1e-9)
                # kernel-stage-only throughput: the device seconds actually
                # spent producing (count, overflow, buffer) triples --
                # attributable separately from staging/pack/decode; the
                # front-end (extract + pack worker seconds) is reported as
                # its own split so the Amdahl bottleneck is visible (the
                # stage dict accumulates over both repeats, hence /2)
                kern_s = stage_l.get("device", 0.0) / 2
                front_l = (stage_l.get("extract", 0.0)
                           + stage_l.get("pack", 0.0)) / 2
                kern_rate = lst.emitted_cliques / max(kern_s, 1e-9)
                emit(f"listing/{gname}/k{k}/{backend}/dev{n}", t_l,
                     f"emitted={lst.emitted_cliques};"
                     f"cliques_per_s={rate:.0f};"
                     f"kernel_s={kern_s:.3f};"
                     f"kernel_cliques_per_s={kern_rate:.0f};"
                     f"frontend_s={front_l:.3f};"
                     f"compile_s={compile_l:.3f};"
                     f"tune_s={tune_s_l:.3f};tune_hit={tune_hit_l};"
                     f"pack_workers={lst.pack_workers};"
                     f"queue_occ={lst.pack_queue_occupancy:.2f};"
                     f"overflowed={lst.overflowed_tiles};"
                     f"sink_bytes={lst.sink_bytes}")
                records.append({
                    "kind": "listing", "backend": backend,
                    "graph": graph_spec, "k": k, "devices": n,
                    "devices_used": used, "seconds": t_l,
                    "count": lst.emitted_cliques,
                    "cliques_per_s": rate,
                    "kernel_seconds": kern_s,
                    "kernel_cliques_per_s": kern_rate,
                    "frontend_s": front_l,
                    "pack_workers": lst.pack_workers,
                    "pack_queue_occupancy": lst.pack_queue_occupancy,
                    "overflowed_tiles": lst.overflowed_tiles,
                    "sink_bytes": lst.sink_bytes,
                    "kernel_compile_s": compile_l,
                    "tune_s": tune_s_l,
                    "tune_cache_hit": tune_hit_l,
                    "device_flops": sum(lst.device_flops.values()),
                    "device_bytes": sum(lst.device_bytes.values()),
                    "phase": phase,
                })
                if n != 1:
                    continue
                # kernel-stage-only row: device listing time in isolation
                # (emitted may undershoot ref_count when oversize tiles
                # spill to the host -- spills are not kernel-stage work)
                emitted_k, ks_s, sz_s, nb = kernel_stage_listing(k, backend)
                ks_rate = emitted_k / max(ks_s, 1e-9)
                emit(f"listing_kernel/{gname}/k{k}/{backend}/dev1", ks_s,
                     f"emitted={emitted_k};batches={nb};"
                     f"kernel_cliques_per_s={ks_rate:.0f};"
                     f"sizing_s={sz_s:.3f}")
                records.append({
                    "kind": "listing_kernel", "backend": backend,
                    "graph": graph_spec, "k": k, "devices": 1,
                    "devices_used": 1, "seconds": ks_s,
                    "count": emitted_k, "batches": nb,
                    "kernel_cliques_per_s": ks_rate,
                    "sizing_seconds": sz_s,
                })
    records.extend(extra_records or [])
    all_records = records
    if out_json:
        payload = {"graph": graph_spec, "ks": list(ks),
                   "device_counts": counts, "backends": list(backends),
                   "batch_size": batch_size,
                   "parity": not mismatches, "records": records}
        if append and os.path.exists(out_json):
            # second phase of a warm-start experiment: merge this run's
            # records into the cold run's JSON (phase field disambiguates)
            with open(out_json) as f:
                prior = json.load(f)
            payload["records"] = prior.get("records", []) + records
            payload["parity"] = payload["parity"] and prior.get("parity",
                                                                True)
            all_records = payload["records"]
        with open(out_json, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"# wrote {out_json} ({len(payload['records'])} records)",
              file=sys.stderr)
    regressions = diff_against_baseline(records, baseline) if baseline else []
    if mismatches or regressions:
        for k, n, got, want in mismatches:
            print(f"PARITY FAILURE k={k} devices={n}: {got} != {want}",
                  file=sys.stderr)
        for k, n, got, want in regressions:
            print(f"BASELINE REGRESSION k={k} devices={n}: {got} != "
                  f"baseline {want}", file=sys.stderr)
        raise SystemExit(1)
    if assert_warm is not None:
        assert_warm_start(all_records, assert_warm)


def diff_against_baseline(records, baseline_path):
    """Compare this run's counts against a committed baseline JSON.

    Matches records on (kind, graph, k, devices, batch) -- counts must
    agree across backends by construction, so the backend is deliberately
    NOT part of the key: a lax run is diffed against a pallas-era baseline
    and vice versa.  ``batch`` (None for the static sweeps) keys the
    mutation benchmark's per-batch snapshots, whose counts evolve with the
    seeded churn.  Any count disagreement is flagged -- the regression
    gate of the CI bench-smoke job (the committed baseline is
    BENCH_pr10.json).  Records present on only one side are counted in
    the summary line but not fatal (the suites may differ in scope).
    """
    with open(baseline_path) as f:
        base = json.load(f)["records"]

    def key(r):
        return (r.get("kind", "count"), r["graph"], r["k"], r["devices"],
                r.get("batch"))

    base_by_key = {key(r): r for r in base}
    mismatches = []
    compared = 0
    run_only = 0
    for r in records:
        b = base_by_key.get(key(r))
        if b is None:
            run_only += 1
            continue
        compared += 1
        if r["count"] != b["count"]:
            mismatches.append((r["k"], r["devices"], r["count"], b["count"]))
    base_only = len(base) - compared
    print(f"# baseline {baseline_path}: {compared} records compared, "
          f"{len(mismatches)} count mismatches "
          f"({run_only} run-only / {base_only} baseline-only skipped)",
          file=sys.stderr)
    return mismatches


def assert_warm_start(records, factor):
    """The warm-start contract of the persistent tuning cache.

    ``records`` must hold both a ``phase == "cold"`` and a
    ``phase == "warm"`` population (two processes sharing one
    ``--tune-cache`` dir, the second run ``--append``-ed).  Asserts

    * every warm autotune record answered from the cache
      (``tune_cache_hit``, i.e. no live microbenchmark re-ran), and
    * the warm phase's summed one-time costs
      (``tune_s + kernel_compile_s``) are at least ``factor`` x smaller
      than the cold phase's -- the persisted records + XLA compilation
      cache actually skipped the measurements and the compiles.

    Counts across the phases are compared too: a warm process must
    reproduce the cold process's answers byte-for-byte.
    """
    def one_time(rs):
        return sum(r.get("kernel_compile_s", 0.0) + r.get("tune_s", 0.0)
                   for r in rs)

    cold = [r for r in records if r.get("phase") == "cold"]
    warm = [r for r in records if r.get("phase") == "warm"]
    if not cold or not warm:
        print("WARM-START FAILURE: need both cold- and warm-phase records "
              f"(got {len(cold)} cold / {len(warm)} warm)", file=sys.stderr)
        raise SystemExit(1)
    failures = []
    cold_by_key = {(r.get("kind"), r["graph"], r["k"], r["devices"]): r
                   for r in cold}
    for r in warm:
        key = (r.get("kind"), r["graph"], r["k"], r["devices"])
        c = cold_by_key.get(key)
        if c is not None and r["count"] != c["count"]:
            failures.append(f"count drift {key}: warm {r['count']} != "
                            f"cold {c['count']}")
        # a hit is owed only where the cold phase actually measured
        # something (e.g. k=3 counting is closed-form: no kernel, no
        # backend resolution, nothing to hit)
        if (r.get("backend") == "autotune"
                and not r.get("tune_cache_hit")
                and c is not None and c.get("tune_s", 0.0) > 0):
            failures.append(f"warm record {key} missed the tuning cache "
                            "(live microbenchmark re-ran)")
    cold_s, warm_s = one_time(cold), one_time(warm)
    ratio = cold_s / max(warm_s, 1e-9)
    print(f"# warm-start: cold tune+compile {cold_s:.3f}s, warm "
          f"{warm_s:.3f}s ({ratio:.1f}x, need >= {factor:g}x)",
          file=sys.stderr)
    if ratio < factor:
        failures.append(f"warm one-time costs only {ratio:.1f}x cheaper "
                        f"than cold (need >= {factor:g}x)")
    if failures:
        for msg in failures:
            print(f"WARM-START FAILURE: {msg}", file=sys.stderr)
        raise SystemExit(1)


# ---------------------------------------------------------------------------
# Dynamic graphs: incremental plan repair vs from-scratch rebuild (--mutate)
# ---------------------------------------------------------------------------

def bench_mutate(graph_spec="rmat:12", ks=(5,), n_batches=5, churn=0.01,
                 order="hybrid", seed=20250808, out_json=None, baseline=None,
                 append=False, assert_repair=None):
    """Edge-churn sweep: ``churn`` fraction of m mutated across
    ``n_batches`` seeded insert/delete batches (half inserts / half
    deletes each), every batch followed by an incremental
    :func:`repair_plan` AND a from-scratch :func:`pipeline.build_plan`
    of the mutated graph.  The touched-neighborhood closure is a
    constant factor wider than the batch itself (a deleted hub edge
    retires every tile over its common neighborhood), so the per-batch
    fraction is ``churn / n_batches`` -- on rmat:12 the default 1% total
    keeps every batch safely under the ``CHURN_THRESHOLD`` fallback.

    Per batch the sweep verifies (exits non-zero on any violation):

    * counts from the repaired plan == counts from the scratch plan for
      every k,
    * listed clique rows byte-identical (canonically sorted) between the
      two plans, and
    * the per-batch :func:`delta_cliques` gained/lost rows compose the
      previous snapshot's rows into exactly the new snapshot's rows.

    ``repair_s`` vs ``rebuild_s`` in the emitted records is the
    amortization claim of repro.delta (DESIGN.md 13); ``assert_repair``
    enforces ``sum(rebuild_s) / sum(repair_s) >= FACTOR`` over the
    repaired (non-fallback) batches -- the BENCH_pr10.json acceptance
    gate.  The batch stream is deterministic in ``seed``, so committed
    per-batch counts are diffable by :func:`diff_against_baseline`.
    """
    from repro.core import ebbkc, pipeline
    from repro.core.graph import apply_edge_batch
    from repro.delta import delta_cliques, repair_plan, rows_diff, rows_union
    from repro.delta.query import rows_sorted
    from repro.launch.clique import load_graph

    g = load_graph(graph_spec)
    gname = graph_spec.replace(":", "").replace(",", "-")
    rng = np.random.default_rng(seed)
    plan, build0_s = timed(pipeline.build_plan, g, order, repeat=2)
    emit(f"mutate/{gname}/plan_build", build0_s,
         f"n={g.n};m={g.m};order={order}")
    prev_rows = {k: rows_sorted(ebbkc.list_cliques(g, k, order=order,
                                                   plan=plan)[0])
                 for k in ks}
    records = []
    failures = []
    repair_total = rebuild_total = 0.0
    n_repaired = 0
    for b in range(n_batches):
        half = max(1, round(g.m * churn / (2 * n_batches)))
        # inserts: rejection-sample pairs not already edges (canonical
        # u < v), so the batch's nominal churn is not diluted by no-ops
        present = set(map(int, g.edge_keys()))
        ins = []
        while len(ins) < half:
            u, v = (int(x) for x in rng.integers(0, g.n, 2))
            if u == v:
                continue
            u, v = min(u, v), max(u, v)
            if u * g.n + v not in present:
                present.add(u * g.n + v)
                ins.append((u, v))
        dele = g.edges[rng.choice(g.m, half, replace=False)]
        g2 = apply_edge_batch(g, insert=np.asarray(ins, np.int64),
                              delete=dele)
        (plan2, info), repair_s = timed(repair_plan, plan, g2, order,
                                        repeat=2)
        scratch, rebuild_s = timed(pipeline.build_plan, g2, order, repeat=2)
        if not info.rebuilt:
            repair_total += repair_s
            rebuild_total += rebuild_s
            n_repaired += 1
        for k in ks:
            r_rep = ebbkc.count(g2, k, order=order, plan=plan2)
            r_scr = ebbkc.count(g2, k, order=order, plan=scratch)
            rows = rows_sorted(
                ebbkc.list_cliques(g2, k, order=order, plan=plan2)[0])
            srows = rows_sorted(
                ebbkc.list_cliques(g2, k, order=order, plan=scratch)[0])
            if r_rep.count != r_scr.count:
                failures.append(f"batch {b} k={k}: repaired count "
                                f"{r_rep.count} != scratch {r_scr.count}")
            if not np.array_equal(rows, srows):
                failures.append(f"batch {b} k={k}: repaired listing rows "
                                "differ from scratch rows")
            d, delta_s = timed(delta_cliques, plan, plan2, info, k,
                               order=order)
            composed = rows_union(rows_diff(prev_rows[k], d.lost), d.gained)
            if not np.array_equal(rows_sorted(composed), rows):
                failures.append(f"batch {b} k={k}: delta does not compose "
                                "prev snapshot into new snapshot")
            prev_rows[k] = rows
            speedup = rebuild_s / max(repair_s, 1e-9)
            emit(f"mutate/{gname}/k{k}/batch{b}", repair_s,
                 f"count={r_rep.count};rebuild_s={rebuild_s:.4f};"
                 f"repair_speedup={speedup:.2f};churn={info.churn:.4f};"
                 f"rebuilt={info.rebuilt};touched={info.touched_new.size};"
                 f"inserted={info.n_insert};deleted={info.n_delete};"
                 f"gained={d.gained.shape[0]};lost={d.lost.shape[0]};"
                 f"delta_query_s={delta_s:.4f}")
            records.append({
                "kind": "mutate", "graph": graph_spec, "k": k,
                "devices": 1, "batch": b, "order": order,
                "seconds": repair_s, "count": r_rep.count,
                "repair_s": repair_s, "rebuild_s": rebuild_s,
                "plan_build_s": rebuild_s,
                "repair_speedup": speedup,
                "churn": info.churn, "rebuilt": info.rebuilt,
                "touched_edges": int(info.touched_new.size),
                "inserted": info.n_insert, "deleted": info.n_delete,
                "delta_gained": int(d.gained.shape[0]),
                "delta_lost": int(d.lost.shape[0]),
                "delta_query_s": delta_s,
                "rows_identical": bool(np.array_equal(rows, srows)),
            })
        g, plan = g2, plan2
    agg = rebuild_total / max(repair_total, 1e-9)
    emit(f"mutate/{gname}/summary", repair_total,
         f"batches={n_batches};repaired={n_repaired};"
         f"rebuild_total_s={rebuild_total:.3f};"
         f"aggregate_repair_speedup={agg:.2f}")
    if out_json:
        payload = {"graph": graph_spec, "ks": list(ks),
                   "n_batches": n_batches, "churn": churn, "order": order,
                   "seed": seed, "parity": not failures,
                   "aggregate_repair_speedup": agg, "records": records}
        if append and os.path.exists(out_json):
            with open(out_json) as f:
                prior = json.load(f)
            prior["records"] = prior.get("records", []) + records
            prior["parity"] = prior.get("parity", True) and not failures
            prior["aggregate_repair_speedup"] = agg
            payload = prior
        with open(out_json, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"# wrote {out_json} ({len(payload['records'])} records)",
              file=sys.stderr)
    if baseline:
        for k, n, got, want in diff_against_baseline(records, baseline):
            failures.append(f"baseline regression k={k} devices={n}: "
                            f"{got} != baseline {want}")
    if assert_repair is not None:
        if n_repaired < n_batches:
            failures.append(f"{n_batches - n_repaired} batches took the "
                            "rebuild fallback (repair gate needs the "
                            "repair path)")
        if agg < assert_repair:
            failures.append(f"aggregate repair speedup {agg:.2f}x < "
                            f"required {assert_repair:g}x")
    if failures:
        for msg in failures:
            print(f"MUTATE FAILURE: {msg}", file=sys.stderr)
        raise SystemExit(1)


# ---------------------------------------------------------------------------
# Geometry autotuner: tuned-vs-default side-by-side (the --tune sweep)
# ---------------------------------------------------------------------------

def bench_tune(graph_spec="rmat:12", ks=(5,), budget_s=20.0):
    """Run the budgeted geometry search, then time tuned vs default.

    The default row is measured FIRST (before the search persists its
    record) so its ``None`` knobs resolve to the hardcoded defaults, not
    to the freshly tuned record.  Both rows run the listing path on the
    same prebuilt plan, best of 2 (first pays any new-shape compiles).
    Returns the records (kind ``tune_geometry``); the coordinate
    descent's > 2% hysteresis means the tuned geometry never loses to
    the defaults by more than measurement noise on the tuning workload.
    """
    import dataclasses as _dc

    from repro.core import ebbkc, pipeline
    from repro.launch.clique import load_graph
    from repro.tune import search as tune_search

    g = load_graph(graph_spec)
    gname = graph_spec.replace(":", "").replace(",", "-")
    plan = pipeline.build_plan(g, order="hybrid")
    records = []

    def run_listing(k, geom):
        def go():
            return ebbkc.list_cliques(
                g, k, backend="jax", plan=plan,
                engine_kwargs=dict(
                    devices=1, batch_size=geom.batch_size,
                    bins=geom.bins, cap_policy=geom.cap_policy,
                    max_capacity=geom.max_capacity,
                    pack_workers=geom.pack_workers,
                    prefetch=geom.prefetch))
        (_, lst), t = timed(go, repeat=2)
        return lst, t

    for k in ks:
        l = k - 2
        lst_d, t_d = run_listing(k, tune_search.Geometry())
        rec = tune_search.tune_geometry("list", l, budget_s=budget_s)
        tuned = tune_search.geometry_from_record(rec)
        lst_t, t_t = run_listing(k, tuned)
        if lst_t.emitted_cliques != lst_d.emitted_cliques:
            print(f"PARITY FAILURE tune k={k}: tuned "
                  f"{lst_t.emitted_cliques} != default "
                  f"{lst_d.emitted_cliques}", file=sys.stderr)
            raise SystemExit(1)
        speedup = t_d / max(t_t, 1e-9)
        for variant, lst, t in (("default", lst_d, t_d),
                                ("tuned", lst_t, t_t)):
            geom = tuned if variant == "tuned" else tune_search.Geometry()
            emit(f"tune/{gname}/k{k}/{variant}", t,
                 f"emitted={lst.emitted_cliques};"
                 f"cliques_per_s={lst.emitted_cliques / max(t, 1e-9):.0f};"
                 f"t_policy={geom.t_policy};batch_size={geom.batch_size};"
                 f"cap_policy={geom.cap_policy};"
                 f"max_capacity={geom.max_capacity};"
                 f"pack_workers={geom.pack_workers}"
                 + (f";speedup_vs_default={speedup:.2f};"
                    f"search_s={rec.data['search_s']:.2f};"
                    f"evals={rec.data['evals']}"
                    if variant == "tuned" else ""))
            records.append({
                "kind": "tune_geometry", "variant": variant,
                "graph": graph_spec, "k": k, "devices": 1,
                "seconds": t, "count": lst.emitted_cliques,
                "cliques_per_s": lst.emitted_cliques / max(t, 1e-9),
                "geometry": _dc.asdict(geom),
                "kernel_compile_s": lst.kernel_compile_s,
                "tune_s": lst.tune_s,
            })
        records[-1]["speedup_vs_default"] = speedup
        records[-1]["search_s"] = rec.data["search_s"]
        records[-1]["search_evals"] = rec.data["evals"]
    return records


# ---------------------------------------------------------------------------
# Fig 11: space costs of the engine structures
# ---------------------------------------------------------------------------

def bench_space():
    from repro.core import engine_jax
    for name, g in graph_suite().items():
        binned, t = timed(engine_jax.bin_tiles, g, 5)
        tile_bytes = sum(p.A.nbytes + p.cand.nbytes
                         for p in binned.values())
        graph_bytes = g.edges.nbytes + g.indptr.nbytes + g.indices.nbytes
        emit(f"space/{name}", t,
             f"graph_bytes={graph_bytes};tile_bytes={tile_bytes};"
             f"ratio={tile_bytes / max(graph_bytes, 1):.2f}")


# ---------------------------------------------------------------------------
# Fig 12: scalability -- runtime vs graph size (RMAT scaling)
# ---------------------------------------------------------------------------

def bench_scalability():
    from repro.data import rmat_graph
    k = 5
    for scale in (10, 11, 12, 13):
        g = rmat_graph(scale, 6, seed=7)
        r, t = timed(ebbkc.count, g, k, order="hybrid", et_t=3)
        emit(f"scalability/rmat{scale}/k{k}", t,
             f"n={g.n};m={g.m};count={r.count}")


# ---------------------------------------------------------------------------
# Device engine: kernel-path comparison + roofline projection
# ---------------------------------------------------------------------------

def bench_device_engine():
    import jax.numpy as jnp
    from repro.core import engine_jax

    g = graph_suite()["ba3k"]
    k = 5
    ref = ebbkc.count(g, k).count
    binned, t_pack = timed(engine_jax.bin_tiles, g, k)
    bins_desc = ":".join(f"T{T}x{p.A.shape[0]}" for T, p in binned.items())
    emit(f"device/pack/ba3k/k{k}", t_pack, f"bins={bins_desc}")
    total = 0
    n_tiles = 0
    flops_mxu = 0
    for T, packed in binned.items():
        A, cand = jnp.asarray(packed.A), jnp.asarray(packed.cand)
        (hard, nv, t, f), dt = timed(
            engine_jax.count_packed, A, cand, k - 2, et=True)
        total += engine_jax.combine_counts(hard, nv, t, f, k - 2, True)
        n_tiles += packed.A.shape[0]
        flops_mxu += packed.A.shape[0] * 2 * T ** 3  # dense-tile matmul path
        emit(f"device/count/ba3k/k{k}/T{T}", dt,
             f"tiles={packed.A.shape[0]}")
    assert total == ref, (total, ref)
    # roofline projection: MXU path at 197 TFLOP/s
    peak = 197e12
    emit(f"device/roofline/ba3k/k{k}", flops_mxu / peak,
         f"tiles={n_tiles};mxu_flops={flops_mxu};"
         f"projected_tpu_seconds={flops_mxu / peak:.3e}")


ALL = [
    bench_dataset_stats, bench_kclique_runtime, bench_ablation,
    bench_ordering_time, bench_edge_orderings, bench_rule2, bench_et_t,
    bench_parallel, bench_pipeline_stages, bench_space, bench_scalability,
    bench_device_engine, bench_dispatch,
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("benches", nargs="*",
                    help="bench function names to run (default: all)")
    ap.add_argument("--devices", default=None,
                    help="comma list of device counts, e.g. 1,4: run the "
                         "multi-device dispatch sweep only")
    ap.add_argument("--graph", default="rmat:12",
                    help="graph spec for the dispatch sweep")
    ap.add_argument("--k", default="5",
                    help="comma list of clique sizes for the dispatch sweep")
    ap.add_argument("--json", default=None,
                    help="write dispatch-sweep records to this JSON file")
    ap.add_argument("--list", action="store_true", dest="with_listing",
                    help="also benchmark the emission subsystem per "
                         "(k, devices, backend): e2e + kernel-stage "
                         "cliques/s + emission stats")
    ap.add_argument("--backend", default="auto",
                    help="comma list of kernel backends to sweep "
                         "(auto/pallas/lax/autotune), e.g. lax,pallas")
    ap.add_argument("--baseline", default=None,
                    help="committed baseline JSON (e.g. BENCH_pr4.json); "
                         "any count mismatch vs matching records exits "
                         "non-zero")
    ap.add_argument("--batch-size", type=int, default=256,
                    help="tile batch size for the dispatch sweep -- applied "
                         "to BOTH the e2e rows and the kernel-stage row, so "
                         "their in-run comparison stays apples-to-apples "
                         "(counts are batch-size-invariant, so baseline "
                         "diffs are unaffected)")
    ap.add_argument("--tune-cache", default=None, metavar="DIR",
                    help="persistent autotuner directory (repro.tune): "
                         "tuning records + XLA compilation cache shared "
                         "across processes; also settable via "
                         "REPRO_TUNE_CACHE")
    ap.add_argument("--tune", action="store_true",
                    help="with --devices: also run the budgeted geometry "
                         "search and record tuned-vs-default listing rows "
                         "side by side")
    ap.add_argument("--tune-budget", type=float, default=20.0,
                    help="search budget in seconds for --tune")
    ap.add_argument("--mutate", action="store_true",
                    help="run the dynamic-graph sweep instead: seeded "
                         "insert/delete batches on --graph, incremental "
                         "plan repair timed against a from-scratch rebuild "
                         "with byte-identical counts/listing rows enforced "
                         "at every batch")
    ap.add_argument("--mutate-batches", type=int, default=5,
                    help="number of edge-churn batches for --mutate")
    ap.add_argument("--mutate-churn", type=float, default=0.01,
                    help="total fraction of m mutated across the sweep "
                         "(split evenly over the batches, half inserts / "
                         "half deletes each)")
    ap.add_argument("--mutate-order", default="hybrid",
                    choices=["truss", "hybrid"],
                    help="edge ordering for --mutate (color always "
                         "rebuilds, so it is not a repair benchmark)")
    ap.add_argument("--mutate-seed", type=int, default=20250808,
                    help="RNG seed for the --mutate batch stream (the "
                         "committed baseline's per-batch counts are only "
                         "reproducible under the same seed)")
    ap.add_argument("--assert-repair", type=float, default=None,
                    metavar="FACTOR",
                    help="with --mutate: require the aggregate "
                         "rebuild_s/repair_s over all batches to be >= "
                         "FACTOR (exits non-zero otherwise)")
    ap.add_argument("--phase", default=None, choices=["cold", "warm"],
                    help="tag this run's records (cold = first process on "
                         "a tune cache, warm = a later one)")
    ap.add_argument("--append", action="store_true",
                    help="merge this run's records into an existing --json "
                         "file instead of overwriting it")
    ap.add_argument("--assert-warm", type=float, default=None,
                    metavar="FACTOR",
                    help="after the sweep, require the warm-phase records' "
                         "summed tune_s+kernel_compile_s to be >= FACTOR x "
                         "smaller than the cold phase's (reads the merged "
                         "--json records; exits non-zero on violation)")
    args = ap.parse_args()
    if args.tune_cache:
        from repro import tune
        tune.configure(args.tune_cache)
    print("name,us_per_call,derived")
    if args.mutate:
        ks = tuple(int(x) for x in args.k.split(","))
        bench_mutate(graph_spec=args.graph, ks=ks,
                     n_batches=args.mutate_batches,
                     churn=args.mutate_churn, order=args.mutate_order,
                     seed=args.mutate_seed, out_json=args.json,
                     baseline=args.baseline, append=args.append,
                     assert_repair=args.assert_repair)
        return
    if args.devices:
        counts = [int(x) for x in args.devices.split(",")]
        # XLA_FLAGS must be in the environment before the backend
        # initializes; forge enough virtual CPU devices for the sweep
        os.environ.setdefault(
            "XLA_FLAGS",
            f"--xla_force_host_platform_device_count={max(counts)}")
        ks = tuple(int(x) for x in args.k.split(","))
        extra = (bench_tune(graph_spec=args.graph, ks=ks,
                            budget_s=args.tune_budget)
                 if args.tune else None)
        bench_dispatch(graph_spec=args.graph, ks=ks, device_counts=counts,
                       out_json=args.json, with_listing=args.with_listing,
                       baseline=args.baseline,
                       backends=tuple(args.backend.split(",")),
                       batch_size=args.batch_size, phase=args.phase,
                       append=args.append, assert_warm=args.assert_warm,
                       extra_records=extra)
        return
    wanted = set(args.benches)
    for fn in ALL:
        if wanted and fn.__name__ not in wanted:
            continue
        fn()


if __name__ == "__main__":
    from repro import tune

    tune.enable_compilation_cache()
    main()
