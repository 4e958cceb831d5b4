"""Laguna-S-2.1's family's cell on the CPU, from added files alone
(``tinycell_laguna.py``): one traced run through signal + serve + proxy
against ``benchmarks/laguna_moe_reference.py`` given the same share, and the
three controls of its stated precisions through ``benchmarks/control.py``.

``slow``: outside tier-1, for ``test_bm_mla_rehearsal.py``'s reason: it
starts serve processes, and ``test_bm_rehearsal.py`` asserts after each of
its runs that none is left on the machine.  Tier-1 holds the same cell to the
same limits in one process: tests/test_laguna_moe.py,
``test_the_tiny_cell_is_correct_as_stated_and_not_under_a_control``.  Run
this file alone, or with ``-p no:xdist``."""

import pytest

pytestmark = pytest.mark.slow

import tinycell_laguna
from test_bm_rehearsal import last_line, read_control, run_cell

CELL = tinycell_laguna.CELL
LIMITS = tinycell_laguna.CONFIG["correct"]["limits"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tinycell_laguna.build(str(tmp_path_factory.mktemp("lagunaroot")))


def test_the_cell_is_correct_as_stated_and_reads_the_ledgers_counts(root):
    result, lines = last_line(run_cell(root, CELL, 1))
    assert result["correct"] is True and result["failed"] == 0
    # the counts come from the dispatch records: there on the CPU too;
    # every device metric of the cell is absent, never zero
    assert set(result["metrics"]) == {"moe_held_share_pct.context",
                                      "moe_imbalance.context"}
    # one of 2 chips that share each layer holds about half the assignments
    assert 30.0 < result["metrics"]["moe_held_share_pct.context"]["value"] \
        < 70.0
    text = "\n".join(lines)
    assert f"cache_bytes_per_token {tinycell_laguna.CACHE_BYTES} by" in text
    assert text.count(": holds") == 5
    assert "compiles inside the window: 0" in text


@pytest.fixture(scope="module")
def stated(root):
    return read_control(root, cell=CELL)


def test_the_program_as_stated_is_correct(stated):
    assert all(r["correct"] for r in stated)
    assert all(r["cache_bytes_per_token"] == tinycell_laguna.CACHE_BYTES
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
        # width alone (8 layers x 3 KV heads)
        assert all(r["cache_bytes_per_token"] == 24 * (32 + 8) for r in rows)
    else:
        assert all(r["cache_bytes_per_token"] == tinycell_laguna.CACHE_BYTES
                   for r in rows)
        assert all(r["echo_prompt"] > LIMITS["echo_prompt"] for r in rows)
