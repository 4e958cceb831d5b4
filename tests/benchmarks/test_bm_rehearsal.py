"""run.py end to end on the CPU, on cells made only of added files: a tiny
configuration, tiny mixes, an added end-to-end metric and an added
per-layer metric, none of which edits a file the benchmark has.  Then the
controls of ``correct`` through the same stack at tiny size: what
``benchmarks/control.py`` reads on the chip at 7B when a limit is set.  Last,
a cell of a second model family (``tiny-moe``, unquantised) whose reference,
cache statement and window counters all come from files the test's root
adds.  (One file, so that one worker runs these stacks one after another.)"""

import json
import os
import statistics
import subprocess
import sys

import pytest

import tinycell

REPO = tinycell.REPO
RUN = os.path.join(REPO, "benchmarks", "run.py")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run_cell(root, cell, trace, env_platform="cpu", seconds="3"):
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env.pop("XLA_FLAGS", None)
    if env_platform:
        env["JAX_PLATFORMS"] = env_platform
    return subprocess.run(
        [sys.executable, RUN, "--root", root, "--workload", cell, "--seed",
         str(2**31 + 5), "--seconds", seconds, "--trace", str(trace)],
        cwd=REPO, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        timeout=300)


def children_left():
    out = subprocess.run(["ps", "-eo", "pid,args"], stdout=subprocess.PIPE,
                         text=True).stdout
    return [line for line in out.splitlines()
            if "serve_wrapper.py" in line or "reference_child.py" in line
            or ("p2p_llm_tunnel_tpu.cli" in line and "bench-" in line)]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tinycell.build(str(tmp_path_factory.mktemp("cellroot")))


def last_line(proc):
    assert proc.returncode == 0, proc.stderr.decode()[-2000:]
    lines = proc.stdout.decode().strip().splitlines()
    return json.loads(lines[-1]), lines


def test_an_added_open_cell_runs_and_prints_the_contracts_line(root):
    result, lines = last_line(run_cell(root, "tiny.tiny-open", 0))
    assert set(result) == RESULT_KEYS
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 20
    # the cell's end-to-end metrics, the added one among them
    assert set(result["metrics"]) == {"ttft_p50_ms", "ttft_p60_ms", "setup_s"}
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    # a CPU rehearsal names its platform and has no device number
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["memory_peak_bytes"] is None
    assert "busy_s" not in result["device"] and "breakdown" not in result
    text = "\n".join(lines)
    assert "ttft_p60_ms: p60 over" in text  # the sample count is printed
    assert text.count("correct: ") >= 3 and "limit 0.05: holds" in text
    assert "compiles inside the window: 0" in text
    assert children_left() == []


def test_an_added_closed_cell_traced_reports_per_layer_metrics_only(root):
    result, _ = last_line(run_cell(root, "tiny.tiny-closed", 1))
    assert set(result) == RESULT_KEYS
    assert result["correct"] is True
    # device metrics are absent on the CPU, never zero
    assert "decode_step_ctr_dev_ms.closed" not in result["metrics"]
    assert "decode_roofline.closed" not in result["metrics"]
    assert "out_tok_per_s" not in result["metrics"]
    assert children_left() == []


def test_without_a_tpu_the_run_fails_and_prints_no_result(root):
    proc = run_cell(root, "tiny.tiny-open", 0, env_platform=None)
    assert proc.returncode != 0
    assert not proc.stdout.decode().strip().splitlines()[-1].startswith("{")
    assert children_left() == []


def test_an_unknown_cell_is_an_error(root):
    proc = run_cell(root, "tiny.no-such-mix", 0)
    assert proc.returncode != 0 and b"no workload" in proc.stderr


def test_alone_in_a_directory_the_benchmark_refuses(tmp_path):
    """Only BENCHMARK.json and the files under ``paths``: no program."""
    import shutil

    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, str(tmp_path / "benchmarks" / "run.py"),
         "--workload", "mistral-7b.chat-open", "--seed", "1", "--seconds",
         "1", "--trace", "0"], cwd=tmp_path, env=dict(os.environ),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, timeout=60)
    assert proc.returncode != 0
    assert not any(line.startswith(b'{"correct"')
                   for line in proc.stdout.splitlines())


# ---- the controls of ``correct`` --------------------------------------------

CONTROL = os.path.join(tinycell.REPO, "benchmarks", "control.py")
NUMBERS = ("echo_prompt", "echo_decode", "traffic_decode", "traffic_prefill")
#: tiny: 2 layers x (K, V) x 2 heads x 16 wide, in bfloat16
TINY_CACHE_BYTES = 2 * 2 * 2 * 16 * 2


def read_control(root, *args, cell="tiny.tiny-open"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, CONTROL, "--root", root, "--workload", cell,
         "--seeds", "11,12,13", *args],
        cwd=tinycell.REPO, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, timeout=300)
    assert proc.returncode == 0, proc.stderr.decode()[-2000:]
    rows = json.loads(proc.stdout.decode().strip().splitlines()[-1])["rows"]
    assert [r["seed"] for r in rows] == [11, 12, 13]
    return rows


def mean(rows, name):
    return statistics.mean(r[name] for r in rows)


@pytest.fixture(scope="module")
def stated(root):
    return read_control(root)


def test_the_program_as_stated_is_correct(stated):
    assert all(r["correct"] for r in stated)
    assert all(r["cache_bytes_per_token"] == TINY_CACHE_BYTES for r in stated)
    assert all(r[n] < 0.008 for r in stated for n in NUMBERS)


def test_an_int8_cache_fails_by_its_width_alone(root, stated):
    """``--kv-quant int8`` where the configuration states bfloat16: the
    log-probabilities read as before (the error sits under the floor that
    bfloat16 activations set), the bytes a cached token takes do not."""
    rows = read_control(root, "--serve-args=--kv-quant int8")
    assert not any(r["correct"] for r in rows)
    assert all(r["cache_bytes_per_token"] < TINY_CACHE_BYTES for r in rows)
    for n in NUMBERS:
        assert mean(rows, n) < 1.4 * mean(stated, n)


def test_int8_activations_fail_on_every_path(root, stated):
    """``--quant w8a8``: whole-prompt prefill, chunked prefill and decode
    all read well above the program as stated."""
    rows = read_control(root, "--serve-args=--quant w8a8")
    for n in NUMBERS:
        assert mean(rows, n) > 1.4 * mean(stated, n), n


def test_int8_activations_in_prefill_alone_show_on_the_ladder(root, stated):
    """``--prefill-act-quant``: decode stays as stated, so only the numbers
    that read prefill's own logits move: the echoed prompt and the first
    tokens of the ladder's prefixes."""
    rows = read_control(root, "--serve-args=--prefill-act-quant")
    for n in ("echo_prompt", "traffic_prefill"):
        assert mean(rows, n) > 1.4 * mean(stated, n), n
    for n in ("echo_decode", "traffic_decode"):
        assert mean(rows, n) < 1.25 * mean(stated, n), n


def test_int4_weights_in_the_references_place_fail(root):
    rows = read_control(root, "--weight-bits", "4")
    assert not any(r["correct"] for r in rows)
    assert all(r["echo_prompt"] > 0.05 for r in rows)


# ---- a second model family, from added files alone ---------------------------

def test_the_second_familys_cell_is_made_of_added_files_only(root):
    """Nothing under ``benchmarks/`` is shadowed: what the root holds by a
    name the repository has is a byte-for-byte copy, and the family's own
    files have names the repository has not."""
    theirs = os.path.join(REPO, "benchmarks")
    added = []
    for dirpath, _dirs, files in os.walk(os.path.join(root, "benchmarks")):
        for name in files:
            mine = os.path.join(dirpath, name)
            rel = os.path.relpath(mine, os.path.join(root, "benchmarks"))
            if not os.path.exists(os.path.join(theirs, rel)):
                added.append(rel)
                continue
            with open(mine, "rb") as a, open(os.path.join(theirs, rel),
                                             "rb") as b:
                assert a.read() == b.read(), rel
    assert {"moe_reference.py", "configs/tiny-moe.json",
            "layer_metrics/counter_ratio.py",
            "layer_metrics/decode_fill_ctr_pct.json"} <= set(added)


def test_the_second_family_is_correct_as_stated_and_reads_its_own_counters(
        root):
    """One traced run: the reference is the configuration's own module, the
    cache statement is that module's, and the per-layer value comes from
    counters that only the metric's own file names."""
    result, lines = last_line(run_cell(root, tinycell.MOE_CELL, 1))
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"decode_fill_ctr_pct"}
    assert 0.0 < result["metrics"]["decode_fill_ctr_pct"]["value"] <= 100.0
    text = "\n".join(lines)
    assert text.count("limit 0.05: holds") == 4
    assert f"cache_bytes_per_token {TINY_CACHE_BYTES} by" in text
    assert children_left() == []


def test_the_second_family_fails_with_4_bit_weights_in_the_references_place(
        root):
    """The control of bfloat16 weights here: the family's own reference
    with its weights rounded to 4 bits."""
    rows = read_control(root, "--weight-bits", "4", cell=tinycell.MOE_CELL)
    assert not any(r["correct"] for r in rows)
    assert all(r[n] > 0.05 for r in rows for n in NUMBERS)


def test_the_second_family_fails_by_width_alone_for_an_int8_cache(root):
    rows = read_control(root, "--serve-args=--kv-quant int8",
                        cell=tinycell.MOE_CELL)
    assert not any(r["correct"] for r in rows)
    assert all(r["cache_bytes_per_token"] < TINY_CACHE_BYTES for r in rows)
    assert all(r[n] < 0.05 for r in rows for n in NUMBERS)
