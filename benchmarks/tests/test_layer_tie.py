"""The tie between a cell and the per-layer metrics it reports (PR 46):
a metric's file defines one, a cell's file lists the definitions that were
there before it, a metric that comes after a cell names that cell in its
own file, `BENCHMARK.json` mirrors the result, and `run.metrics_for` is
the one place that follows the tie. The refusals, the files-only seventh
cell and the files-only new instrument run on a temporary copy of
`benchmarks/`; the last test holds the fold itself against the parent
commit where there is a git repository to ask."""

import json
import os
import shutil
import subprocess

import pytest

from run import RunFailed, metrics_for

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
KEYS = ("name", "layer", "unit", "better", "source", "moves")
CELLS = {"omb-1024p-100b.steady": 30, "ref-compose.sync": 23,
         "omb-1024p-100b.saturate": 12, "omb-1024p-100b-keyed.zipf": 37,
         "omb-100p-1kb.steady": 41, "omb-16p-1kb.tail": 27}
PARENT = "28dbc862d9f60dff3ca8a8bb86e425a0a117a90d"  # the tree PR 46 folded


def load(*parts: str, here: str = BENCH) -> dict:
    with open(os.path.join(here, *parts)) as f:
        return json.load(f)


def names_in(kind: str, here: str = BENCH) -> list[str]:
    return sorted(f[:-5] for f in os.listdir(os.path.join(here, kind))
                  if f.endswith(".json"))


def definition(m: dict) -> str:
    """What a metric reads and how it is filed, without its name."""
    return json.dumps([m["reader"]] + [m[k] for k in KEYS[1:]],
                      sort_keys=True)


def listing() -> dict:
    """metric -> the cells that report it, in `BENCHMARK.json`'s order of
    cells."""
    bench = load("BENCHMARK.json", here=ROOT)
    out: dict = {}
    for w in bench["workloads"]:
        for m in metrics_for(w["name"]):
            out.setdefault(m["name"], []).append(w["name"])
    return out


def test_benchmark_json_is_held_to_the_files():
    bench = load("BENCHMARK.json", here=ROOT)
    entries = {e["name"]: e for e in bench["per_layer"]}
    assert len(entries) == len(bench["per_layer"]) <= 128
    assert sorted(entries) == names_in("layer_metrics")
    assert sorted(w["name"] for w in bench["workloads"]) \
        == names_in("workloads") == sorted(CELLS)
    listed = listing()
    reports = {c: load("workloads", f"{c}.json")["end_to_end"] for c in CELLS}
    e2e = {e["name"]: e for e in bench["end_to_end"]}
    for name, e in entries.items():
        m = load("layer_metrics", f"{name}.json")
        assert {k: m[k] for k in KEYS} == {k: e[k] for k in KEYS}, name
        assert set(m) - {"workloads"} == set(KEYS) | {"reader"}, name
        # an entry's cells are the cells that report it, and there is one
        assert e["workloads"] == listed[name] != [], name
        for cell in e["workloads"]:
            assert m["moves"] in reports[cell], (name, cell)
            assert cell in e2e[m["moves"]].get("workloads", list(CELLS))
    assert set(listed) == set(entries)  # nothing reported without an entry
    # today every tie is a cell's list: the 19 files that name cells (two
    # tier-1 tests of tests/ compare that key with the entry) name only
    # cells that list them too
    for cell in CELLS:
        assert [m["name"] for m in metrics_for(cell)] \
            == load("workloads", f"{cell}.json")["layer_metrics"]


def test_one_file_a_definition():
    """No two files read the same thing under the same filing - but the
    one pair `tests/test_parked_fetch.py` pins (it opens
    `tail.fetch_parts_per_request.json` and holds its entry to the tail
    cell alone), which a PR that may edit `tests/` folds."""
    by_def: dict = {}
    for name in names_in("layer_metrics"):
        by_def.setdefault(definition(load("layer_metrics", f"{name}.json")),
                          []).append(name)
    assert sorted(ns for ns in by_def.values() if len(ns) > 1) == [
        ["consume.fetch_parts_per_request", "tail.fetch_parts_per_request"]]
    assert len(by_def) == 63


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_metrics_for_follows_the_cells_list(cell):
    names = load("workloads", f"{cell}.json")["layer_metrics"]
    got = metrics_for(cell)
    assert [m["name"] for m in got] == names and len(names) == CELLS[cell]
    for m in got:
        assert os.path.isfile(os.path.join(
            BENCH, "readers", f"{m['reader']['kind']}.py")), m["name"]


def test_the_renamed_table_names_what_is_there():
    """`layer_metrics_renamed.json`: every name that left in PR 46 ->
    the name that reads the same thing now."""
    table = load("layer_metrics_renamed.json")
    here = set(names_in("layer_metrics"))
    assert len(table) == 76 and not set(table) & here
    assert set(table.values()) <= here and len(set(table.values())) == 31


@pytest.fixture
def copy(tmp_path):
    for kind in ("workloads", "layer_metrics"):
        shutil.copytree(os.path.join(BENCH, kind), tmp_path / kind)
    return str(tmp_path)


def texts(here: str) -> dict:
    """Every cell and metric file under `here`, as text."""
    out = {}
    for kind in ("workloads", "layer_metrics"):
        for n in names_in(kind, here):
            with open(os.path.join(here, kind, f"{n}.json")) as f:
                out[kind, n] = f.read()
    return out


def edit_cell(here: str, cell: str, **changes) -> None:
    spec = load("workloads", f"{cell}.json", here=here)
    spec.update(changes)
    with open(os.path.join(here, "workloads", f"{cell}.json"), "w") as f:
        json.dump(spec, f)


def test_a_seventh_cell_is_its_own_file_and_nothing_else(copy):
    """A later cell reads the definitions that are there: its file names
    them, and no file that was there is touched."""
    before = texts(copy)
    spec = load("workloads", "omb-16p-1kb.tail.json", here=copy)
    want = ["dataplane.queue_wait_ms", "tail.wake_late_ms",
            "consume.serve_ms", "host.interp_wake_late_ms"]
    spec.update(name="omb-16p-1kb.seventh", traffic="seventh",
                layer_metrics=want)
    with open(os.path.join(copy, "workloads", "omb-16p-1kb.seventh.json"),
              "w") as f:
        json.dump(spec, f)
    got = metrics_for("omb-16p-1kb.seventh", here=copy)
    assert [m["name"] for m in got] == want
    assert got[1] == load("layer_metrics", "tail.wake_late_ms.json")
    assert before.items() <= texts(copy).items()  # no file touched


def test_a_new_instrument_for_cells_that_are_there_is_its_own_file(copy):
    """A later `tracing` PR's metric names the accepted cells it is read
    in; they report it after their own lists, and no file is touched."""
    before = texts(copy)
    sat, steady = "omb-1024p-100b.saturate", "omb-1024p-100b.steady"
    new = dict(load("layer_metrics", "saturate.launch_ms.json", here=copy),
               name="saturate.a_new_wait_ms", workloads=[sat])
    with open(os.path.join(copy, "layer_metrics",
                           "saturate.a_new_wait_ms.json"), "w") as f:
        json.dump(new, f)
    assert [m["name"] for m in metrics_for(sat, here=copy)] == load(
        "workloads", f"{sat}.json")["layer_metrics"] + [new["name"]]
    assert [m["name"] for m in metrics_for(steady, here=copy)] == load(
        "workloads", f"{steady}.json")["layer_metrics"]
    assert before.items() <= texts(copy).items()  # no file touched
    # it moves the rate, which steady does not report: named there, refused
    new["workloads"] = [sat, steady]
    with open(os.path.join(copy, "layer_metrics",
                           "saturate.a_new_wait_ms.json"), "w") as f:
        json.dump(new, f)
    with pytest.raises(RunFailed, match="saturate.a_new_wait_ms moves "
                                        "acked_msgs_per_s"):
        metrics_for(steady, here=copy)


@pytest.mark.parametrize("listed, says", [
    (["saturate.launch_ms", "no.such_metric"],
     "no.such_metric, and layer_metrics/no.such_metric.json is not there"),
    # the saturate cell reports no ack median
    (["saturate.launch_ms", "dataplane.launch_ms"],
     "dataplane.launch_ms moves produce_ack_p50_ms, which the cell does "
     "not report"),
    (["saturate.launch_ms", "saturate.drain_ms", "saturate.launch_ms"],
     "saturate.launch_ms 2 times"),
])
def test_a_list_that_cannot_be_followed_is_refused_by_name(copy, listed, says):
    cell = "omb-1024p-100b.saturate"
    edit_cell(copy, cell, layer_metrics=listed)
    with pytest.raises(RunFailed) as e:
        metrics_for(cell, here=copy)
    assert str(e.value).startswith(f"cell {cell}: ") and says in str(e.value)


def test_a_cell_that_reports_nothing_per_layer_is_refused(copy):
    spec = dict(load("workloads", "ref-compose.sync.json", here=copy),
                name="ref-compose.bare", layer_metrics=[])
    with open(os.path.join(copy, "workloads", "ref-compose.bare.json"),
              "w") as f:
        json.dump(spec, f)
    with pytest.raises(RunFailed, match="cell ref-compose.bare: no "
                                        "per-layer metric"):
        metrics_for("ref-compose.bare", here=copy)


def test_a_file_under_another_metrics_name_is_refused(copy):
    shutil.copy(os.path.join(copy, "layer_metrics", "saturate.drain_ms.json"),
                os.path.join(copy, "layer_metrics", "saturate.launch_ms.json"))
    with pytest.raises(RunFailed, match="holds the metric saturate.drain_ms"):
        metrics_for("omb-1024p-100b.saturate", here=copy)


def test_the_fold_lost_nothing_the_parent_read():
    """(cell, reader, moves, unit, better, source, layer) over every cell:
    this tree's set is the parent's plus the three the cap had taken from
    the tail cell. Needs the repository; a bare checkout skips."""
    def tup(cell: str, m: dict) -> tuple:
        return (cell, definition(m))

    try:
        paths = subprocess.run(
            ["git", "ls-tree", "--name-only", PARENT,
             "benchmarks/layer_metrics/"], cwd=ROOT, check=True,
            capture_output=True, text=True).stdout.split()
    except (OSError, subprocess.CalledProcessError):
        pytest.skip("no git repository that knows the parent commit")
    old = set()
    for path in paths:
        m = json.loads(subprocess.run(
            ["git", "show", f"{PARENT}:{path}"], cwd=ROOT, check=True,
            capture_output=True, text=True).stdout)
        old |= {tup(cell, m) for cell in m["workloads"]}
    new = {tup(cell, m) for cell in CELLS for m in metrics_for(cell)}
    assert len(paths) == 128 and len(old) == 167 and old <= new
    tail = "omb-16p-1kb.tail"
    assert new - old == {tup(tail, load("layer_metrics", f"{n}.json"))
                         for n in ("dataplane.drain_ms",
                                   "dataplane.stage_fill", "store.fsync_ms")}
