"""Per-device placement accounting on four virtual devices.

``Stats.device_cost`` holds the scheduler cost model's cost of the tiles
each device was given, and every ``device/stage`` span names the device
it staged to.  The four-device checks run once, in a subprocess started
with ``--xla_force_host_platform_device_count=4`` (as
``test_device_decode`` does); each parametrised case reads its own part
of that run's report.
"""

import json
import os
import subprocess
import sys

import pytest

from repro.core.engine_np import Stats
from repro.obs import metrics as obs_metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

GRAPHS = {"rmat8": (8, 8, 7), "rmat9": (9, 4, 3)}  # scale, edge factor, seed
KS = (4, 5, 6)

_FOUR_DEVICES = """
import json
import jax
from bench import reference
from repro.core import engine_jax, listing, pipeline
from repro.core.engine_np import Stats
from repro.data import rmat_graph
from repro.launch.mesh import make_local_mesh
from repro.obs import trace
from repro.runtime import dispatch as dsp
from repro.runtime.clique_scheduler import tile_costs

GRAPHS = %(graphs)r
KS = %(ks)r
assert jax.device_count() == 4
report = {}


def batches(g, k):
    return [b for b in pipeline.stream_batches(g, k, batch_size=8)
            if isinstance(b, pipeline.TileBatch)]


def costs(bs, l):
    return [float(tile_costs(b.sizes, b.nedges, l).sum()) for b in bs]


def placed(stats, bs, l, placements):
    # what device_cost should read, where batch i went to placements[i]
    want = {}
    for d, c in zip(placements, costs(bs, l)):
        want[d] = want.get(d, 0.0) + c
    return {"cost": {str(d): c for d, c in stats.device_cost.items()},
            "want": {str(d): c for d, c in want.items()},
            "tiles": sorted(stats.device_tiles)}


def stage_devices():
    return [ev["args"].get("device") for ev in trace.events()
            if ev.get("name") == "device/stage"]


g = rmat_graph(8, 8, seed=7)
k, l = 5, 3
bs = batches(g, k)
trace.configure(enabled=True)

# online LPT over four devices
trace.reset()
st = Stats()
disp = dsp.Dispatcher(l, devices=4, stats=st)
for b in bs:
    disp.submit(b)
disp.finish()
report["lpt"] = dict(placed(st, bs, l, disp.placements),
                     stage=stage_devices(), placements=disp.placements)

# offline LPT: scheduler bins forced onto devices
trace.reset()
st = Stats()
_, info = dsp.dispatch_scheduled(bs, l, devices=4, stats=st)
device_of = {bi: d for d, bins in enumerate(info["device_bins"]) for bi in bins}
report["scheduled"] = dict(placed(st, bs, l,
                                  [device_of[i] for i in range(len(bs))]),
                           stage=stage_devices(),
                           placements=info["placements"])

# the shard_map path: each batch's rows split over the mesh's shards
trace.reset()
st = Stats()
mesh = make_local_mesh((4, 1), axes=("data", "model"))
dsp.dispatch_scheduled(bs, l, mesh=mesh, stats=st)
report["mesh"] = {"cost": {str(d): c for d, c in st.device_cost.items()},
                  "total": sum(costs(bs, l)),
                  "tiles": sorted(st.device_tiles),
                  "stage": stage_devices()}

# the listing dispatcher places by the same cost model
trace.reset()
st = Stats()
ldisp = dsp.ListDispatcher(l, devices=4, stats=st,
                           sink=listing.CallbackSink(lambda rows: None))
for b in bs:
    ldisp.submit(b)
ldisp.finish()
ldisp.close()
report["list"] = dict(placed(st, bs, l, ldisp.placements),
                      stage=stage_devices(), placements=ldisp.placements)
trace.configure(enabled=False)

# whole jobs: devices="all" against one device and the plain reference
for name, (scale, ef, seed) in GRAPHS.items():
    g = rmat_graph(scale, ef, seed=seed)
    for k in KS:
        many = engine_jax.count(g, k, devices="all", batch_size=8,
                                plan_cache=False)
        one = engine_jax.count(g, k, devices=1, batch_size=8,
                               plan_cache=False)
        report[f"count-{name}-k{k}"] = {
            "all": int(many.count), "one": int(one.count),
            "reference": int(reference.answer(g.n, g.edges, k, "count")),
            "cost_keys": sorted(many.stats.device_cost),
            "tile_keys": sorted(many.stats.device_tiles)}
print(json.dumps(report))
"""


@pytest.fixture(scope="module")
def four_devices():
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
                   [ROOT, os.path.join(ROOT, "src")]),
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("REPRO_BACKEND", None)
    env.pop("REPRO_FAULT_PLAN", None)
    script = _FOUR_DEVICES % {"graphs": GRAPHS, "ks": KS}
    proc = subprocess.run([sys.executable, "-c", script], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("path", ["lpt", "scheduled", "list"])
def test_device_cost_is_the_placed_cost(four_devices, path):
    got = four_devices[path]
    assert set(got["cost"]) == set(got["want"]) == {"0", "1", "2", "3"}
    for d, c in got["want"].items():
        assert got["cost"][d] == pytest.approx(c, rel=1e-12), d
    assert sorted(int(d) for d in got["cost"]) == got["tiles"]


def test_device_cost_on_the_mesh_path(four_devices):
    got = four_devices["mesh"]
    assert set(got["cost"]) == {"0", "1", "2", "3"}
    assert sum(got["cost"].values()) == pytest.approx(got["total"], rel=1e-12)
    assert sorted(int(d) for d in got["cost"]) == got["tiles"]


@pytest.mark.parametrize("path", ["lpt", "scheduled", "list", "mesh"])
def test_stage_span_names_the_device(four_devices, path):
    got = four_devices[path]
    if path == "mesh":  # one SPMD step over every shard
        assert got["stage"] and set(got["stage"]) == {-1}
    else:
        assert got["stage"] == got["placements"]
        assert set(got["stage"]) == {0, 1, 2, 3}


@pytest.mark.parametrize("graph", sorted(GRAPHS))
@pytest.mark.parametrize("k", KS)
def test_count_on_all_devices_is_exact(four_devices, graph, k):
    got = four_devices[f"count-{graph}-k{k}"]
    assert got["all"] == got["one"] == got["reference"]
    assert got["cost_keys"] == got["tile_keys"] == [0, 1, 2, 3]


@pytest.mark.parametrize("mine, theirs, want", [
    ({0: 1.5}, {0: 1.0, 1: 2.0}, {0: 2.5, 1: 2.0}),
    ({}, {3: 4.0}, {3: 4.0}),
    ({2: 0.5}, {}, {2: 0.5}),
])
def test_device_cost_merges_as_a_dict(mine, theirs, want):
    a = Stats(device_cost=dict(mine))
    a.merge(Stats(device_cost=dict(theirs)))
    assert a.device_cost == want
    reg = obs_metrics.Registry()
    obs_metrics.observe_stats(a, "repro_engine", reg)
    got = {m.labels: m.value for m in reg.collect()
           if m.name == "repro_engine_device_cost_total"}
    assert got == {(("key", str(d)),): v for d, v in want.items()}
