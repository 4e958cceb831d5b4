"""The harness takes another cell with no edit to a file that is there
(ISSUE 42).  A later PR brings its cell as new files and entries only, and
may not touch ``benchmarks/`` or ``tests/benchmarks/`` as they stand: so a
test there that pins how many cells, lists or places the benchmark has today
refuses every such PR.  Here the benchmark and its tests are copied, the copy
gets what such a PR brings, and the copy's own tests run on it: the contract
whole, the start-up entries, and EVERY family's ``test_bm_*_roofline.py`` as
it is written (ISSUE 50: one of them held its cell's metrics to be the last
of ``per_layer``, which the selection of ISSUE 42 did not run, and the next
cell's metrics went in as files of no list).

Then the text of every test file is held to the rule that made the room: no
count of cells, configurations or per-layer metrics, and no place in those
lists, be it a literal or a slice counted from the end, is compared."""

import glob
import json
import os
import re
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

CONFIG, MIX = "room-7b", "chat-burst"
CELL = CONFIG + "." + MIX
#: What runs on the copy: the contract whole, the nine start-up entries
#: against every cell, and every family's roofline file whole (found in the
#: copy, so the next family's is run with no edit here): those name their
#: cells' own metrics.
SELECTED = ["test_bm_contract.py", "test_bm_startup_phase.py"]
FAMILIES = "test_bm_*_roofline.py"
KEYWORDS = ("test_bm_contract or declared_for_every_cell or moves_setup_s "
            "or _roofline")


def load(path):
    with open(path) as f:
        return json.load(f)


def dump(body, path):
    with open(path, "w") as f:
        json.dump(body, f, indent=1)


def add_a_cell(root):
    """What a ``model_config`` PR brings, as files that were not there and
    entries appended: a configuration (mistral-7b's file under another
    name), a traffic file (chat-open's lengths and rate under gamma
    arrivals, PERF.md section 7's first kept cell), the cell, its name at
    the end of the lists it reads, and two per-layer metrics after the last:
    a tenth of the start-up layer and one of its own."""
    data = os.path.join(root, "benchmarks")
    bench = load(os.path.join(root, "BENCHMARK.json"))

    body = load(os.path.join(data, "configs", "mistral-7b.json"))
    dump(dict(body, name=CONFIG), os.path.join(data, "configs",
                                               CONFIG + ".json"))
    (entry,) = [c for c in bench["configs"] if c["name"] == "mistral-7b"]
    bench["configs"].append(dict(
        entry, name=CONFIG, file=f"benchmarks/configs/{CONFIG}.json"))

    mix = load(os.path.join(data, "traffic", "chat-open.json"))
    dump(dict(mix, name=MIX, arrivals={"dist": "gamma", "cv": 2.0}),
         os.path.join(data, "traffic", MIX + ".json"))
    bench["workloads"].append({
        "name": CELL, "config": CONFIG, "traffic": MIX, "chips": 1,
        "why": "open loop, gamma arrivals cv 2 at chat-open's rate and "
               "lengths: admission and the queue-wait tail under bursts"})

    listed = {"ttft_p50_ms", "tpot_p90_ms", "queue_wait_p90_ms",
              "decode_fill_pct.open"}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in listed or m.get("moves") == "setup_s":
            m["workloads"].append(CELL)

    for new, old, cells in (
            ("setup_room_s", "setup_after_ready_s",
             [w["name"] for w in bench["workloads"]]),
            ("queue_wait_p90_ms.burst", "queue_wait_p90_ms", [CELL])):
        shutil.copy(os.path.join(data, "layer_metrics", old + ".json"),
                    os.path.join(data, "layer_metrics", new + ".json"))
        (entry,) = [m for m in bench["per_layer"] if m["name"] == old]
        bench["per_layer"].append(dict(entry, name=new, workloads=cells))
    dump(bench, os.path.join(root, "BENCHMARK.json"))
    return bench


def test_an_eighth_cell_is_taken_with_no_edit_to_a_file_that_is_there(
        tmp_path):
    root = str(tmp_path)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    skip = shutil.ignore_patterns("__pycache__")
    shutil.copytree(os.path.join(REPO, "benchmarks"),
                    os.path.join(root, "benchmarks"), ignore=skip)
    shutil.copytree(os.path.join(REPO, "tests", "benchmarks"),
                    os.path.join(root, "tests", "benchmarks"), ignore=skip)
    before = {w["name"] for w in load(
        os.path.join(root, "BENCHMARK.json"))["workloads"]}
    bench = add_a_cell(root)
    assert {w["name"] for w in bench["workloads"]} == before | {CELL}

    env = {k: v for k, v in os.environ.items()
           if not k.startswith("PYTEST_")}
    # the benchmark and its tests are the copy's, the program (which a
    # family's test may hold a yardstick to) the repository's
    env.update(PYTHONPATH=os.pathsep.join([root, REPO]), JAX_PLATFORMS="cpu")
    families = sorted(os.path.basename(f) for f in glob.glob(
        os.path.join(root, "tests", "benchmarks", FAMILIES)))
    files = [os.path.join("tests", "benchmarks", f)
             for f in SELECTED + families]
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-v", "-p", "no:cacheprovider",
         "-p", "no:randomly", "-k", KEYWORDS] + files,
        cwd=root, env=env, capture_output=True, text=True, timeout=180)
    out = proc.stdout
    assert proc.returncode == 0, out[-6000:] + proc.stderr[-2000:]
    # each family's file ran, and in it the test that names its cell's own
    for name in families:
        assert re.search(rf"{re.escape(name)}::\S+ PASSED", out), name
    named = re.findall(r"(test_bm_\w+_roofline)\.py::"
                       r"test_the_cells_metrics_are_the_ones_the_issue_names "
                       r"PASSED", out)
    assert {"test_bm_bd_roofline", "test_bm_swa_roofline",
            "test_bm_ssm_roofline", "test_bm_granite_roofline"} <= set(named)
    # the copy's tests ran on the copy, and on the new entries
    for case in (f"test_cells[{CELL}] PASSED",
                 f"test_configurations[{CONFIG}] PASSED",
                 "test_per_layer_metrics[setup_room_s] PASSED",
                 "test_per_layer_metrics[queue_wait_p90_ms.burst] PASSED",
                 "test_each_of_the_nine_is_declared_for_every_cell"
                 "[setup_to_ready_s] PASSED"):
        assert case in out, case

    # the new mix needs no code: the one generator reads it
    code = ("import json; from benchmarks import traffic; "
            f"m = json.load(open('benchmarks/traffic/{MIX}.json')); "
            "p = traffic.make_plan(m, 2147483659, 48, 32000); "
            "print(len([r for r in p.requests if 0 <= r.due < 48]))")
    made = subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                          capture_output=True, text=True, timeout=120)
    assert made.returncode == 0, made.stderr[-2000:]
    assert int(made.stdout) == round(5.6 * 48)


#: A count of the benchmark's cells, configurations or per-layer metrics,
#: or of the cells a test derived from them, beside a literal, on either
#: side; a place in those lists given by a literal; and, anywhere on a
#: line that names one of the lists, an index or a slice counted from the
#: end (of the list itself or of what was gathered from it).
LISTS = r"""(BENCH|bench)\[["'](workloads|configs|per_layer)["']\]"""
COUNTED = rf"len\(\s*({LISTS}|\w*CELLS\w*)\s*\)"
CMP = r"(==|!=|<=|>=|<|>)"
PINS = [re.compile(rf"{COUNTED}\s*{CMP}\s*\d"),
        re.compile(rf"\d\s*{CMP}\s*{COUNTED}"),
        re.compile(rf"{LISTS}\s*\[\s*-?\d*\s*:?\s*-?\d"),
        re.compile(rf"{LISTS}.*\[\s*-\s*[\w(]")]


def test_no_test_compares_a_count_or_a_place_in_the_lists_with_a_literal():
    pinned = []
    for path in sorted(glob.glob(os.path.join(REPO, "tests", "benchmarks",
                                              "*.py"))):
        if os.path.samefile(path, __file__):    # the examples below
            continue
        with open(path) as f:
            for number, line in enumerate(f, 1):
                if any(p.search(line) for p in PINS):
                    pinned.append(f"{os.path.basename(path)}:{number}: "
                                  f"{line.strip()}")
    assert not pinned, "\n".join(pinned)
    # the patterns find what they are for
    for line in ('assert len(BENCH["workloads"]) == 7',
                 "assert len(CELLS) == 5 and ok",
                 'assert 5 <= len(bench["configs"])',
                 'assert set(BENCH["per_layer"][-9:]) == nine',
                 'first = BENCH["workloads"][0]',
                 # what PR 44's test held: written in two pieces, so that a
                 # search of this directory for the form finds no test
                 'assert [m["name"] for m in bench["per_layer"]][-'
                 'len(NEW):] == NEW',
                 'all(m["workloads"] == [C] for m in bench["per_layer"][-'
                 'len(NEW):])',
                 'last = bench["configs"][-n:]'):
        assert any(p.search(line) for p in PINS), line
    for line in ('assert four <= max(1, len(BENCH["workloads"]) // 4)',
                 'for cell in BENCH["workloads"]:',
                 'assert len(names) == len(BENCH["per_layer"])',
                 'assert BENCH["command"][-1] == "benchmarks/run.py"',
                 'assert [m["workloads"] for m in bench["per_layer"] '
                 'if m["name"] in NEW] == [[CELL]] * len(NEW)'):
        assert not any(p.search(line) for p in PINS), line
