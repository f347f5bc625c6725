"""The cell ``wordcount.w4`` and its two readers, on the CPU: the
entries found by name, a rehearsal on four of the eight virtual devices
(``correct`` true, and false under the control), both readers reading
numbers off a rehearsed traced run and ``None`` where the program has
no such counter (a parent commit's). No number here is a device
number."""

import importlib.util
import json
import os
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_BENCH = os.path.join(_ROOT, "chipbench")
CELL = "wordcount.w4"
NEW = ("exchange_rows_per_job", "exchange_local_share")
# the cell's shares of metrics that other cells report too
SHARED = ("dispatches_per_job", "fetches_per_job", "compiles_in_window",
          "device_idle_share", "device_busy_ms_per_job", "job_roofline",
          "hbm_peak_share", "oom_ladder_events", "collective_share",
          "exchange_bytes_per_job", "upload_s_per_job",
          "upload_bytes_per_job", "dispatch_call_s_per_job",
          "sync_wait_s_per_job", "fetch_s_per_job", "host_plan_s_per_job",
          "compile_s_in_window", "job_s_max", "transfer_s_per_job",
          "device_idle_s_per_job")
# what a CPU rehearsal cannot read: it has no device plane, no memory
# statistics and no collective in a trace
DEVICE_ONLY = ("device_idle_share", "device_busy_ms_per_job",
               "job_roofline", "hbm_peak_share", "collective_share")


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _bench():
    with open(os.path.join(_ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def readers():
    return {name: _load(os.path.join(_BENCH, "layer_metrics", name + ".py"),
                        f"reader_{name}_w4t") for name in NEW}


# ------------------------------------------------------ the data files

def test_benchmark_json_names_the_cell_and_its_metrics():
    """Every entry is found by its NAME and no position is pinned:
    later cells and metrics follow it."""
    bench = _bench()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("wordcount-zipf-4w", "closed_zipf_2p24", 4)
    w1 = next(w for w in bench["workloads"] if w["name"] == "wordcount.w1")
    # a deployment of its own, with wordcount.w1's job kind and row shapes
    configs = {c["name"]: c for c in bench["configs"]}
    mine, one = (configs[cell["config"]], configs[w1["config"]])
    assert mine["source"] != one["source"]
    assert set(mine["reduced"]) == {"workers", "words_per_worker"}
    with open(os.path.join(_ROOT, mine["file"])) as f:
        mine_file = json.load(f)
    with open(os.path.join(_ROOT, one["file"])) as f:
        one_file = json.load(f)
    assert (mine_file["job"], mine_file["shapes"]) \
        == (one_file["job"], one_file["shapes"])
    assert mine_file["deployment"]["workers"] == cell["chips"]
    assert set(mine_file["reduced"]) == set(mine["reduced"])
    with open(os.path.join(_BENCH, "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    with open(os.path.join(_BENCH, "traffic", w1["traffic"] + ".json")) as f:
        one_chip = json.load(f)
    # four chips, each with wordcount.w1's share, the same mix
    assert traffic["words_per_job"] == 4 * one_chip["words_per_job"] \
        == 1 << 24
    assert (traffic["zipf_s"], traffic["vocabulary"]) == (1.1, 65536)
    assert (traffic["loop"], traffic["clients"]) == ("closed", 1)
    assert traffic["check"] == {"jobs": "all"}
    assert traffic["traced_jobs"] == 3
    assert 1000 <= traffic["rehearse"]["words_per_job"] <= 10000
    for name in NEW:
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        assert (m["source"], m["layer"], m["moves"]) \
            == ("program_counter", "exchange", "records_per_s")
        assert m["workloads"] == [CELL]
    assert {m["name"]: (m["unit"], m["better"]) for m in bench["per_layer"]
            if m["name"] in NEW} == {
        "exchange_rows_per_job": ("count", "lower"),
        "exchange_local_share": ("%", "higher")}
    reported = {m["name"] for m in bench["per_layer"]
                if CELL in m.get("workloads", [CELL])}
    assert reported == set(SHARED) | set(NEW)


# ---------------------------------------------------------- the readers

def test_the_readers_are_silent_without_their_counters(readers):
    """A parent commit's program has neither counter; ``run["stats"]``
    then lacks them and nothing is read, nothing raises."""
    parent = {"stats": {"exchanges": 3, "bytes_moved": 96}, "jobs": 3}
    assert all(r.read(parent) is None for r in readers.values())
    no_exchange = {"stats": {"exchanges": 0, "xchg_rows_in": 0,
                             "xchg_rows_local": 0}, "jobs": 3}
    assert all(r.read(no_exchange) is None for r in readers.values())
    run = {"stats": {"exchanges": 3, "xchg_rows_in": 600,
                     "xchg_rows_local": 150}, "jobs": 3}
    assert readers["exchange_rows_per_job"].read(run) == 200.0
    assert readers["exchange_local_share"].read(run) == 25.0


# ------------------------------------------------------- run.py end to end

@pytest.fixture
def rehearsal_env(monkeypatch):
    # what run.py sets for a rehearsal is restored afterwards: the worker
    # goes on to other tests
    for var in ("THRILL_TPU_HOST_RADIX", "THRILL_TPU_SORT_U32",
                "THRILL_TPU_PACK_MOVE"):
        monkeypatch.setenv(var, "")
        monkeypatch.delenv(var)
    monkeypatch.setattr(sys, "path", list(sys.path))


def _run_py(tag):
    return _load(os.path.join(_BENCH, "run.py"), f"chipbench_run_w4{tag}")


def test_a_traced_rehearsal_on_four_devices_reads_both_counters(
        rehearsal_env, capsys):
    run_py = _run_py("t")
    seen = {}
    reader = run_py.load_module("layer_metrics", "exchange_local_share")
    real = reader.read
    reader.read = lambda run: (seen.update(run), real(run))[1]
    try:
        assert run_py.main(["--workload", CELL, "--seed", str(2**31 + 41),
                            "--seconds", "0.05", "--trace", "1",
                            "--rehearse"]) == 0
    finally:
        reader.read = real
    captured = capsys.readouterr()
    last = json.loads(captured.out.strip().splitlines()[-1])
    assert "count=4" in captured.err.splitlines()[0]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] == 3 and last["check"]["jobs_compared"] == 3
    assert set(last["reported"]) \
        == (set(SHARED) | set(NEW)) - set(DEVICE_ONLY)
    stats, jobs = seen["stats"], seen["jobs"]
    # one exchange a job, counted once; the pre-phase's runs go in
    assert stats["exchanges"] == jobs == 3
    rows = run_py.load_module("layer_metrics",
                              "exchange_rows_per_job").read(seen)
    assert rows == stats["xchg_rows_in"] / 3
    assert 0 < rows <= 4096
    # 4,096 words are too few for the registers to pay: a hash partition
    # over four workers keeps a quarter where it is
    assert stats["dup_detect_exchanges"] == 0
    assert real(seen) == pytest.approx(25.0, abs=4.0)
    # the counters added no fetch: one a job, the table's counts
    assert last["counts"]["device_fetches"] == 3


def test_the_control_at_four_devices_comes_out_not_correct(rehearsal_env,
                                                          capsys):
    run_py = _run_py("c")
    assert run_py.main(["--workload", CELL, "--seed", str(2**31 + 43),
                        "--seconds", "0.05", "--trace", "0", "--rehearse",
                        "--control"]) == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["correct"] is False and last["failed"] == 0
    assert last["check"]["rows_missing"]["value"] > 0
