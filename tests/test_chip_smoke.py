"""``chip_smoke.py``, rehearsed on the CPU.

The script's results count only on a TPU; here its control flow, its
refusal to run off the chip, and its row checks are exercised at a tiny
size.
"""
import importlib.util
import json
import os

import numpy as np
import pytest

from repro.core.graph import from_edges

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("argv", [[], ["--four-chips"]])
def test_refuses_to_run_off_the_chip(smoke, capsys, argv):
    assert smoke.main(argv) == 2
    out, err = capsys.readouterr()
    assert out == ""
    assert "no TPU" in err and "'cpu'" in err


def test_cpu_rehearsal_runs_every_phase(smoke, capsys):
    assert smoke.main(["--rehearse", "--scale", "9",
                       "--oracle-scale", "8"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    for tag in ("[a] PASS", "[b] PASS", "[c] PASS"):
        assert any(line.startswith(tag) for line in lines), tag
    last = json.loads(lines[-1])
    assert "ok" not in last and last["rehearsal"] is True
    assert last["device"]["platform"] == "cpu"


def test_four_chip_rehearsal_needs_four_devices(smoke, capsys):
    assert smoke.main(["--rehearse", "--four-chips", "--scale", "8"]) == 2
    assert "needs 4 devices" in capsys.readouterr().err


def _k4():
    return from_edges(6, [(a, b) for a in range(4) for b in range(a + 1, 4)]
                      + [(4, 5)])


@pytest.mark.parametrize("rows,why", [
    ([[0, 1, 2], [0, 1, 3]], None),
    ([[1, 0, 2]], "not sorted"),
    ([[0, 1, 4]], "not adjacent"),
    ([[0, 1, 2], [1, 2, 3], [0, 1, 2]], "listed twice"),
    ([[0, 1, 6]], "out of range"),
])
def test_row_checks(smoke, rows, why):
    rows = np.asarray(rows, dtype=np.int64)
    if why is None:
        smoke.check_rows(_k4(), rows, 3)
        return
    with pytest.raises(smoke.SmokeFailure, match=why):
        smoke.check_rows(_k4(), rows, 3)


def test_delta_oracle_lists_cliques_through_inserted_edges(smoke):
    # g_new already holds the inserted edge (2, 3); its common neighbours
    # 0, 1 and 4 each close a triangle through it, and (0, 1, 2, 3) is
    # the one 4-clique holding it
    g_new = from_edges(5, [(0, 1), (0, 2), (0, 3), (1, 2), (1, 3), (2, 3),
                           (2, 4), (3, 4)])
    np.testing.assert_array_equal(
        smoke.cliques_through(g_new, np.array([[2, 3]]), 3),
        [[0, 2, 3], [1, 2, 3], [2, 3, 4]])
    np.testing.assert_array_equal(
        smoke.cliques_through(g_new, np.array([[2, 3]]), 4), [[0, 1, 2, 3]])
