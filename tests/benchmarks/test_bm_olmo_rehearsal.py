"""The cell of delta-rule layers beside attention, an MLP a layer, on the CPU,
from added files alone (``tinycell_olmo.py``): one traced run through signal + serve +
proxy against ``benchmarks/olmo_hybrid_reference.py``, and the three
controls of its stated precisions through ``benchmarks/control.py``.

``slow``: outside tier-1, for ``test_bm_mla_rehearsal.py``'s reason: it
starts serve processes, and ``test_bm_rehearsal.py`` asserts after each of
its runs that none is left on the machine.  Tier-1 holds the same cell to the
same limits in one process: tests/test_olmo_hybrid.py,
``test_the_tiny_cell_is_correct_as_stated_and_not_under_a_control``.  Run
this file alone, or with ``-p no:xdist``."""

import pytest

pytestmark = pytest.mark.slow

import tinycell_olmo
from test_bm_rehearsal import last_line, read_control, run_cell

CELL = tinycell_olmo.CELL
LIMITS = tinycell_olmo.CONFIG["correct"]["limits"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tinycell_olmo.build(str(tmp_path_factory.mktemp("olmoroot")))


def test_the_cell_is_correct_as_stated_and_its_device_metrics_are_absent(
        root):
    result, lines = last_line(run_cell(root, CELL, 1))
    assert result["correct"] is True and result["failed"] == 0
    # every per-layer metric of the cell reads the device trace: absent on
    # the CPU, never zero
    assert result["metrics"] == {}
    text = "\n".join(lines)
    assert f"cache_bytes_per_token {tinycell_olmo.CACHE_BYTES} by" in text
    assert text.count(": holds") == 5
    assert "compiles inside the window: 0" in text


@pytest.fixture(scope="module")
def stated(root):
    return read_control(root, cell=CELL)


def test_the_program_as_stated_is_correct(stated):
    assert all(r["correct"] for r in stated)
    assert all(r["cache_bytes_per_token"] == tinycell_olmo.CACHE_BYTES
               for r in stated)


CONTROLS = {
    "weights": ("--weight-bits", "8"),
    "activations": ("--serve-args=--quant a8",),
    "kv_cache": ("--serve-args=--kv-quant int8",),
}


@pytest.mark.parametrize("lowered", sorted(CONTROLS))
def test_each_stated_precision_lowered_is_not_correct(root, stated, lowered):
    rows = read_control(root, *CONTROLS[lowered], cell=CELL)
    assert not any(r["correct"] for r in rows)
    if lowered == "kv_cache":
        # int8 planes and a float32 scale a KV head beside each: by its
        # width alone (2 attention layers x 3 KV heads)
        assert all(r["cache_bytes_per_token"] == 2 * 3 * (32 + 8)
                   for r in rows)
    else:
        assert all(r["cache_bytes_per_token"] == tinycell_olmo.CACHE_BYTES
                   for r in rows)
        assert all(r["echo_prompt"] > LIMITS["echo_prompt"] for r in rows)
