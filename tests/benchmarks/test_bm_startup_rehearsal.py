"""The nine ``setup_*`` per-layer metrics through the whole harness on the
CPU: ``run.py --trace 1`` on the tiny open cell (signal + serve + proxy),
whose serve process writes the start-up journal, exports it on
``/healthz?trace=1`` and is read by ``layer_metrics/startup_phase.py``.
The untraced run of the same cell and seed then gives ``setup_s``, and the
journal's two ends account for it.

``slow``: outside tier-1, for ``test_bm_mla_rehearsal.py``'s reason: it
starts serve processes, and ``test_bm_rehearsal.py`` asserts after each of
its runs that none is left on the machine.  Tier-1 reads the same nine
through ``run.read_layer_metrics`` on a real start's journal in one
process: test_bm_startup_phase.py.  Run this file alone, or with
``-p no:xdist``."""

import json
import os

import pytest

pytestmark = pytest.mark.slow

import tinycell
from test_bm_rehearsal import last_line, run_cell
from test_bm_startup_phase import NINE

CELL = "tiny.tiny-open"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """tinycell's root, its serve process given the threaded AOT phase the
    cells' configurations ask for."""
    root = tinycell.build(str(tmp_path_factory.mktemp("startuproot")))
    path = os.path.join(root, "benchmarks", "configs", "tiny.json")
    with open(path) as f:
        config = json.load(f)
    config["serve"]["env"]["TUNNEL_WARMUP_PAR"] = "2"
    with open(path, "w") as f:
        json.dump(config, f)
    return root


def test_the_nine_read_in_a_traced_run_and_account_for_setup_s(root):
    traced, lines = last_line(run_cell(root, CELL, 1))
    assert traced["correct"] is True and traced["failed"] == 0
    got = {k: v["value"] for k, v in traced["metrics"].items() if k in NINE}
    assert set(got) == set(NINE), "\n".join(lines[-30:])
    parts = (got["setup_imports_s"] + got["setup_backend_s"]
             + got["setup_build_s"] + got["setup_warmup_s"])
    assert 0.97 * got["setup_to_ready_s"] <= parts <= got["setup_to_ready_s"]
    assert got["setup_programs"] >= 3 and got["setup_after_ready_s"] > 0.5
    assert 0 <= got["setup_cache_misses"] <= got["setup_programs"]
    # the untraced run of the same cell prints setup_s: the journal's two
    # ends, the serve process's start to ready and ready to the window,
    # leave only run.py's own seconds before it spawns the stack
    plain, _ = last_line(run_cell(root, CELL, 0))
    setup_s = plain["metrics"]["setup_s"]["value"]
    whole = got["setup_to_ready_s"] + got["setup_after_ready_s"]
    assert abs(setup_s - whole) < 5.0, (setup_s, got)
