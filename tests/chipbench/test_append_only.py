"""The driver's rule for a PR that changes the program, rehearsed in the
sandbox: such a PR may add files under the benchmark's ``paths`` and
entries at the END of the lists of ``BENCHMARK.json``, and nothing else.
``accepted_order.json`` beside this file is the benchmark as the last
`benchmark` PR left it (``accept_order.py`` writes it, by hand); the
committed ``BENCHMARK.json`` has to start with it, list by list. The
cases on a copy in ``tmp_path`` show that a cell which brings nothing but
files and entries at the end passes this check and the data-file checks
of ``test_harness.py``, and that the layout PR 34 was refused for, or an
edit to an entry that is there, fails here before the driver sees it."""

import copy
import importlib.util
import json
import os
import shutil

import pytest

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(os.path.dirname(_HERE))
_spec = importlib.util.spec_from_file_location(
    "chipbench_bench_contract", os.path.join(_HERE, "bench_contract.py"))
contract = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(contract)

# what a PR that adds a cell brings, under names no cell has
CONFIG = {"name": "made-up-deployment", "source": "no source: a test's",
          "file": "chipbench/configs/made-up-deployment.json",
          "reduced": ["points_per_job"], "why": "a test's configuration"}
CELL = {"name": "made-up.w1", "config": "made-up-deployment",
        "traffic": "closed_made_up", "chips": 1, "why": "a test's cell"}
METRIC = {"name": "made_up_per_job", "unit": "count", "better": "lower",
          "source": "program_counter", "layer": "DIA ops and fusion",
          "moves": "records_per_s", "workloads": ["made-up.w1"]}
READER = '''"""A counter outside the ten that a run prints, per job."""


def read(run: dict):
    made = run["stats"].get("made_up")
    return made / run["jobs"] if made and run["jobs"] else None
'''


@pytest.fixture(scope="module")
def accepted():
    with open(os.path.join(_HERE, "accepted_order.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bench():
    return contract.load_bench(_ROOT)


def every_cell_metrics(accepted):
    """The metrics every accepted cell reports: a new cell's too."""
    cells = {w["name"] for w in accepted["workloads"]}
    return [m["name"] for m in accepted["per_layer"]
            if set(m.get("workloads", cells)) >= cells]


def with_new_cell(bench, accepted, put_in=()):
    """``bench`` with the made-up configuration, cell and metric, and the
    cell's name in every every-cell metric's list: at the END of each
    list, or, for the lists named in ``put_in`` (``lists``: the metrics'
    ``workloads``), in front of the last accepted entry, which is where
    PR 34 laid its own out."""
    new = copy.deepcopy(bench)
    for group, entry in (("configs", CONFIG), ("workloads", CELL),
                         ("per_layer", METRIC)):
        where = len(accepted[group]) - 1 if group in put_in \
            else len(new[group])
        new[group].insert(where, copy.deepcopy(entry))
    shared = every_cell_metrics(accepted)
    assert shared
    for m in accepted["per_layer"]:
        if m["name"] in shared:
            listed = next(e for e in new["per_layer"]
                          if e["name"] == m["name"])["workloads"]
            listed.insert(len(m["workloads"]) - 1 if "lists" in put_in
                          else len(listed), CELL["name"])
    return new


@pytest.fixture
def tree(tmp_path, bench):
    """A copy of ``BENCHMARK.json`` and ``chipbench/`` with the new cell's
    FILES added: a configuration, a traffic mix, a reader. No file that
    was there is touched."""
    shutil.copytree(os.path.join(_ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    like = bench["workloads"][0]        # any cell's files will do
    config = next(c for c in bench["configs"] if c["name"] == like["config"])
    shutil.copy(os.path.join(_ROOT, config["file"]),
                tmp_path / CONFIG["file"])
    shutil.copy(os.path.join(_ROOT, "chipbench", "traffic",
                             like["traffic"] + ".json"),
                tmp_path / "chipbench" / "traffic"
                / (CELL["traffic"] + ".json"))
    (tmp_path / "chipbench" / "layer_metrics"
     / (METRIC["name"] + ".py")).write_text(READER)

    def write(new):
        with open(tmp_path / "BENCHMARK.json", "w") as f:
            json.dump(new, f, indent=1)
        return str(tmp_path)
    return write


def test_the_committed_lists_start_with_the_accepted_entries(bench,
                                                             accepted):
    # a `benchmark` PR that edits BENCHMARK.json and forgets
    # accept_order.py is told so here too
    contract.check_append_only(bench, accepted)


def test_a_cell_appended_with_its_files_passes_every_check(tree, bench,
                                                           accepted):
    new = with_new_cell(bench, accepted)
    root = tree(new)
    contract.check_append_only(contract.load_bench(root), accepted)
    contract.check_names_units_and_keys(new)
    contract.check_data_files(root, new)
    contract.check_readers(root, new)
    # and the harness finds all of it by name, with no edit: the cell
    # with its two files, its metrics with their readers, a counter
    # outside the ten a run prints
    run_py = contract.load_run_py(root)
    cell = run_py.load_cell(new, CELL["name"])
    assert cell["config_file"]["job"] and cell["traffic_file"]["rehearse"]
    reported = [m["name"] for m in
                run_py.metrics_of(new, "per_layer", CELL["name"])]
    assert reported == every_cell_metrics(accepted) + [METRIC["name"]]
    read = run_py.load_module("layer_metrics", METRIC["name"]).read
    stats = run_py.stat_deltas({"made_up": 2, "device_dispatches": 1},
                               {"made_up": 8, "device_dispatches": 4})
    assert read({"stats": stats, "jobs": 3}) == 2.0
    assert read({"stats": {}, "jobs": 3}) is None


@pytest.mark.parametrize("put_in", [
    ("configs", "workloads", "per_layer", "lists"),     # PR 34's layout
    ("configs",), ("workloads",), ("per_layer",), ("lists",)],
    ids="+".join)
def test_a_cell_put_in_before_an_accepted_one_fails(tree, bench, accepted,
                                                    put_in):
    new = with_new_cell(bench, accepted, put_in)
    root = tree(new)
    # nothing else is wrong with it
    contract.check_names_units_and_keys(new)
    contract.check_data_files(root, new)
    contract.check_readers(root, new)
    with pytest.raises(AssertionError, match="END of each list"):
        contract.check_append_only(new, accepted)


def _edit_why(b):
    b["workloads"][1]["why"] += " (reworded)"


def _edit_config_source(b):
    b["configs"][0]["source"] += " and more"


def _loosen_a_bound(b):
    b["end_to_end"][0]["bound"] += 0.01


def _change_what_a_metric_moves(b):
    m = b["per_layer"][0]
    m["moves"] = next(e["name"] for e in b["end_to_end"]
                      if e["name"] != m["moves"])


def _take_a_cell_out_of_a_metric(b):
    del b["per_layer"][0]["workloads"][0]


def _retire_a_metric(b):
    del b["per_layer"][0]


def _swap_two_cells(b):
    b["workloads"][0], b["workloads"][1] = \
        b["workloads"][1], b["workloads"][0]


def _lengthen_the_run(b):
    b["run_seconds"] += 1


@pytest.mark.parametrize("edit", [
    _edit_why, _edit_config_source, _loosen_a_bound,
    _change_what_a_metric_moves, _take_a_cell_out_of_a_metric,
    _retire_a_metric, _swap_two_cells, _lengthen_the_run],
    ids=lambda f: f.__name__.strip("_"))
def test_an_accepted_entry_edited_fails(bench, accepted, edit):
    new = with_new_cell(bench, accepted)
    edit(new)
    with pytest.raises(AssertionError, match="takes a `benchmark` PR"):
        contract.check_append_only(new, accepted)
