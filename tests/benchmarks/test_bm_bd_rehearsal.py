"""The block-generation family's cell on the CPU, from added files alone
(``tinycell_bd.py``): one traced run through signal + serve + proxy against
``benchmarks/block_diffusion_reference.py``, and the three controls of its
stated precisions through ``benchmarks/control.py``.

``slow``: outside tier-1, for ``test_bm_mla_rehearsal.py``'s reason: it
starts serve processes, and ``test_bm_rehearsal.py`` asserts after each of
its runs that none is left on the machine.  Tier-1 holds the same cell to the
same limits in one process: tests/test_block_diffusion.py,
``test_the_tiny_cell_is_correct_as_stated_and_not_under_a_control``.  Run
this file alone, or with ``-p no:xdist``."""

import pytest

pytestmark = pytest.mark.slow

import tinycell_bd
from test_bm_rehearsal import last_line, read_control, run_cell

CELL = tinycell_bd.CELL
LIMITS = tinycell_bd.CONFIG["correct"]["limits"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tinycell_bd.build(str(tmp_path_factory.mktemp("bdroot")))


def test_the_cell_is_correct_as_stated_and_reads_the_ledgers_counts(root):
    result, lines = last_line(run_cell(root, CELL, 1))
    assert result["correct"] is True and result["failed"] == 0
    # the counts come from the dispatch records: there on the CPU too;
    # every device metric of the cell (``decode_dev_us_per_token.blockgen``
    # and the rooflines among them) is absent, never zero
    assert set(result["metrics"]) == set(tinycell_bd.LEDGER_METRICS)
    assert "decode_dev_us_per_token.blockgen" not in result["metrics"]
    yielded = result["metrics"]["tokens_per_row_pass.blockgen"]["value"]
    # two passes fill four positions and no pass is a commit alone (PR 48);
    # a finished row's last burst and a short answer's partial block yield
    # less: the answers are 8-16 tokens, 2-4 blocks a request
    assert 1.5 < yielded <= 2.0
    assert result["metrics"]["moe_held_share_pct.context"]["value"] == 100.0
    text = "\n".join(lines)
    assert f"cache_bytes_per_token {tinycell_bd.CACHE_BYTES} by" in text
    assert text.count(": holds") == 5
    assert "compiles inside the window: 0" in text
    assert "per-layer decode_dev_us_per_token.blockgen: nothing to read" \
        in text


@pytest.fixture(scope="module")
def stated(root):
    return read_control(root, cell=CELL)


def test_the_program_as_stated_is_correct(stated):
    assert all(r["correct"] for r in stated)
    assert all(r["cache_bytes_per_token"] == tinycell_bd.CACHE_BYTES
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
        # int8 values and a float32 scale a KV head beside each: by its
        # width (the log-probabilities move too at this size)
        assert all(r["cache_bytes_per_token"] == 6 * (32 + 8) for r in rows)
    else:
        assert all(r["cache_bytes_per_token"] == tinycell_bd.CACHE_BYTES
                   for r in rows)
        assert all(r["echo_prompt"] > LIMITS["echo_prompt"] for r in rows)
