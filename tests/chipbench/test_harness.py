"""The chip benchmark's own arithmetic, on the CPU: the trace reduction,
the byte counts, the generators and references, the data files against
the contract's rules, and ``run.py`` end to end in a rehearsal -- with the
control and with the timed path broken underneath, where ``correct`` has
to come out false. No number here is a device number."""

import glob
import importlib.util
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_BENCH = os.path.join(_ROOT, "chipbench")
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
_UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
_SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def run_py():
    return _load(os.path.join(_BENCH, "run.py"), "chipbench_run")


@pytest.fixture(scope="module")
def reduce_py():
    return _load(os.path.join(_BENCH, "trace_reduce.py"),
                 "chipbench_trace_reduce")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(_ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture
def rehearsal_env(monkeypatch):
    # what run.py sets for a rehearsal is restored afterwards: the worker
    # goes on to other tests
    for var in ("THRILL_TPU_HOST_RADIX", "THRILL_TPU_SORT_U32",
                "THRILL_TPU_PACK_MOVE"):
        monkeypatch.setenv(var, "")
        monkeypatch.delenv(var)
    monkeypatch.setattr(sys, "path", list(sys.path))


def _cells():
    with open(os.path.join(_ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


def _job(run_py, bench, cell):
    c = run_py.load_cell(bench, cell)
    return run_py.load_module("jobs", c["config_file"]["job"]), c


# ---------------------------------------------------------------- trace

@pytest.mark.parametrize("intervals,want", [
    ([(0, 10), (5, 20), (30, 40)], [[0, 20], [30, 40]]),
    ([(5, 6), (0, 10)], [[0, 10]]),
    ([(0, 1), (1, 2)], [[0, 2]]),
    ([(3, 3)], []),
])
def test_union_merges_overlapping_intervals(reduce_py, intervals, want):
    assert reduce_py.union(intervals) == want


def test_self_time_goes_to_the_innermost_operation(reduce_py):
    events = [("while", 0, 100), ("a", 10, 20), ("b", 30, 10),
              ("a", 50, 60), ("c", 120, 10)]
    # the second "a" outlasts its parent and is clipped to it
    assert reduce_py.self_time_by_name(events) == {
        "while": 20.0, "a": 70.0, "b": 10.0, "c": 10.0}


def test_summarize_hand_made_planes(reduce_py):
    planes = {
        "/host:CPU": {"python": [("PjitFunction", 0, 100e9)]},
        "/device:TPU:0": {"XLA Ops": [("fusion", 20e9, 30e9),
                                      ("all-to-all.1", 40e9, 30e9),
                                      ("fusion", 120e9, 30e9)],
                          "Steps": [("step", 0, 1000e9)]},
        "/device:TPU:1": {"XLA Ops": [("fusion", 0, 160e9)]},
    }
    # the harness's clock started 1,000 s before the trace's; the last
    # job's end is laid on the last device operation's end
    s = reduce_py.summarize(planes, [(1000.0, 1100.0), (1110.0, 1160.0)])
    assert s["devices"] == 2 and s["jobs"] == 2
    assert s["window_s"] == pytest.approx(160.0)
    assert s["busy_s_per_device"] == pytest.approx([80.0, 160.0])
    assert s["busy_s"] == pytest.approx(120.0)
    ops = dict(s["device_ops"])
    # the mean over the devices; the collective is the nested child
    assert ops["fusion"] == pytest.approx((20 + 30 + 160) / 2)
    assert ops["all-to-all.1"] == pytest.approx(10 / 2)
    assert s["collective_s"] == pytest.approx(5.0)
    assert s["idle_s_by_label"] == pytest.approx(
        {"in_job.head": 15.0, "in_job.tail": 20.0, "between_jobs": 5.0})
    # the first device's gaps alone: the second is never idle
    assert s["idle_gaps"] == [["in_job.tail", pytest.approx(30.0)],
                              ["in_job.head", pytest.approx(20.0)],
                              ["in_job.tail", pytest.approx(10.0)],
                              ["in_job.head", pytest.approx(10.0)],
                              ["between_jobs", pytest.approx(10.0)]]


@pytest.mark.parametrize("planes,spans", [
    ({"/host:CPU": {"python": [("job", 0, 10)]}}, [(0.0, 1.0)]),
    ({"/device:TPU:0": {"Steps": [("step", 0, 10)]}}, [(0.0, 1.0)]),
    ({"/device:TPU:0": {"XLA Ops": [("fusion", 0, 10)]}}, []),
])
def test_summarize_with_nothing_to_read_reads_nothing(reduce_py, planes,
                                                      spans):
    assert reduce_py.summarize(planes, spans) is None


@pytest.mark.parametrize("op,want", [
    ("%fusion.44 = (u32[8388608]{0:T(1024)}, u32[8388608]{0:T(1024)}) "
     "fusion(pred[8388608]{0:T(1024)} %gte.561), kind=kLoop, calls=%fc.23",
     "fusion.44 u32[8388608] kLoop"),
    ("%while.6 = (u32[]{:T(128)}, u32[8388608]{0:T(1024)}) while(%t), "
     "condition=%c, body=%b", "while.6 u32[]"),
    ("%all-to-all.3 = u8[4,1048576,100]{2,1,0} all-to-all(%x)",
     "all-to-all.3 u8[4,1048576,100]"),
    ("jit_f(3701085079205818048)", "jit_f(3701085079205818048)"),
])
def test_short_name_keeps_name_shape_and_kind(reduce_py, op, want):
    assert reduce_py.short_name(op) == want


@pytest.mark.parametrize("name,want", [
    ("all_to_all.37 u32[4,2097152,23]", True),      # as the v5e named it
    ("all-reduce.11 u32[4]", True),
    ("collective-permute-start.2 u32[8]", True),
    ("all_gather.3 u8[4,16]", True),
    ("fusion.5 u32[8388609,23] kCustom", False),
    ("copy-done.10 u32[4,1,2097152]", False),
])
def test_collectives_are_told_by_name(reduce_py, name, want):
    assert reduce_py.is_collective(name) is want


def test_recorded_chip_trace_cut(reduce_py):
    """A thinned cut of a traced run of terasort.w1 on the v5e (PR 25),
    kept as a text XSpace so that the real loader reads it."""
    path = os.path.join(_BENCH, "testdata", "terasort_w1_cut.xspace.txt")
    with open(os.path.join(_BENCH, "testdata",
                           "terasort_w1_cut.expect.json")) as f:
        expect = json.load(f)
    s = reduce_py.summarize(reduce_py.load_xplane(path, text_proto=True),
                            expect["job_spans_s"])
    assert s["devices"] == expect["devices"] == 1
    assert s["jobs"] == expect["jobs"] == 3
    for key in ("window_s", "busy_s", "collective_s"):
        assert s[key] == pytest.approx(expect[key], rel=1e-9)
    assert 0.0 < s["busy_s"] < s["window_s"]
    assert s["device_ops"] == [[n, pytest.approx(v)]
                               for n, v in expect["device_ops"]]
    assert s["device_ops"][1][0] == "fusion u32[8388608,26] kCustom"
    # the device waits for the upload at the head of every job
    assert s["idle_s_by_label"]["in_job.head"] > 0.9 * (
        s["window_s"] - s["busy_s"])


# ------------------------------------------------- bytes and generators

@pytest.mark.parametrize("cell,want", [
    ("terasort.w1", 1_677_721_600),
    ("wordcount.w1", 100_663_296),
])
def test_min_bytes(run_py, bench, cell, want):
    job, c = _job(run_py, bench, cell)
    table = {"w": np.zeros((1000, 16), np.uint8)}
    got = job.min_bytes(c["traffic_file"], c["config_file"], table)
    if cell.startswith("wordcount"):
        # 24 bytes for every row of the reference's table besides
        assert got == want + 24 * 1000
        assert job.min_bytes(c["traffic_file"], c["config_file"],
                             None) is None
    else:
        assert got == want


@pytest.mark.parametrize("cell", ["terasort.w1", "wordcount.w1"])
def test_generators_are_functions_of_the_seed_alone(run_py, bench, cell):
    job, c = _job(run_py, bench, cell)
    traffic = {**c["traffic_file"], **c["traffic_file"]["rehearse"]}
    big = 2**31 + 11
    a, b = (job.generate(big, traffic, c["config_file"]) for _ in range(2))
    other = job.generate(big + 1, traffic, c["config_file"])
    assert sorted(a) == sorted(b)
    for k in a:
        assert a[k].tobytes() == b[k].tobytes()
    assert any(a[k].tobytes() != other[k].tobytes() for k in a)
    assert len(next(iter(a.values()))) == job.records(traffic)


def test_terasort_reference_refuses_duplicate_keys(run_py):
    job = run_py.load_module("jobs", "terasort")
    key = np.array([[1] * 10, [0] * 10, [1] * 10], np.uint8)
    with pytest.raises(ValueError, match="duplicate keys"):
        job.reference({"key": key, "value": np.zeros((3, 90), np.uint8)}, {})


@pytest.mark.parametrize("kind", ["terasort", "wordcount"])
def test_reference_passes_itself_and_fails_its_control(run_py, bench, kind):
    cell = {"terasort": "terasort.w1", "wordcount": "wordcount.w1"}[kind]
    job, c = _job(run_py, bench, cell)
    traffic = {**c["traffic_file"], **c["traffic_file"]["rehearse"]}
    inp = job.generate(5, traffic, c["config_file"])
    want = job.reference(inp, traffic)
    assert all(v <= lim for v, lim in job.compare(want, want).values())
    bad = job.compare(job.control(inp, traffic), want)
    assert any(v > lim for v, lim in bad.values())


# ------------------------------------------------ data files and contract

def test_every_data_file_loads_and_is_used(bench):
    configs = {os.path.join(_ROOT, c["file"]) for c in bench["configs"]}
    assert configs == set(glob.glob(os.path.join(_BENCH, "configs",
                                                 "*.json")))
    for path in configs:
        with open(path) as f:
            c = json.load(f)
        assert os.path.exists(os.path.join(_BENCH, "jobs", c["job"] + ".py"))
        assert c["guarantees"] and c["shapes"] and c["source"]
    used = {w["traffic"] for w in bench["workloads"]}
    have = {os.path.basename(p)[:-5] for p in
            glob.glob(os.path.join(_BENCH, "traffic", "*.json"))}
    assert used == have
    for name in have:
        with open(os.path.join(_BENCH, "traffic", name + ".json")) as f:
            t = json.load(f)
        assert t["loop"] == "closed" and t["clients"] == 1
    with open(os.path.join(_BENCH, "peaks.json")) as f:
        assert "TPU v5 lite" in json.load(f)["device_kinds"]


def test_names_units_and_keys_are_within_the_contract(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert _NAME.match(c["name"]) and all(map(_NAME.match, c["reduced"]))
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert _NAME.match(w["name"]) and _NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in _SOURCES
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert _NAME.match(m["name"]) and _UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    assert "setup_s" in names


def test_every_per_layer_metric_has_a_reader_and_moves_a_reported_metric(
        bench, run_py):
    cells = [w["name"] for w in bench["workloads"]]
    for m in bench["per_layer"]:
        assert callable(run_py.load_module("layer_metrics", m["name"]).read)
        moved = next(e for e in bench["end_to_end"]
                     if e["name"] == m["moves"])
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert cell in moved.get("workloads", cells)
    for cell in cells:
        assert len(run_py.metrics_of(bench, "end_to_end", cell)) >= 2
        assert run_py.metrics_of(bench, "per_layer", cell)


def test_readers_on_a_made_up_run(run_py):
    """Each reader's arithmetic on round numbers; nothing to read gives
    None, never 0."""
    run = {"trace": {"busy_s": 6.0, "window_s": 8.0, "jobs": 3,
                     "collective_s": 0.3},
           "stats": {"device_dispatches": 9, "device_fetches": 6,
                     "exchanges": 3, "bytes_moved": 3000, "oom_retries": 1,
                     "segment_splits": 0, "host_fallbacks": 0,
                     "admission_spills": 2, "hbm_spills": 0},
           "memory": [{"peak_bytes_in_use": 4e9, "bytes_limit": 16e9},
                      {"peak_bytes_in_use": 8e9, "bytes_limit": 16e9}],
           "jobs": 3, "compiles": 0, "cell": {"chips": 4},
           "peaks": {"hbm_bytes_per_s": 800e9}, "min_bytes": 3.2e9}
    want = {"dispatches_per_job": 3, "fetches_per_job": 2,
            "compiles_in_window": 0, "device_idle_share": 25.0,
            "device_busy_ms_per_job": 2000.0,
            # 0.8e9 B per chip / 800e9 B/s = 1 ms of 2,000 ms
            "job_roofline": 0.05, "hbm_peak_share": 50.0,
            "oom_ladder_events": 3, "collective_share": 5.0,
            "exchange_bytes_per_job": 1000}
    for name, value in want.items():
        read = run_py.load_module("layer_metrics", name).read
        assert read(run) == pytest.approx(value), name
    blind = dict(run, trace=None, memory=[{}], min_bytes=None,
                 stats=dict(run["stats"], exchanges=0))
    for name in ("device_idle_share", "device_busy_ms_per_job",
                 "job_roofline", "collective_share", "hbm_peak_share",
                 "exchange_bytes_per_job"):
        assert run_py.load_module("layer_metrics", name).read(blind) is None


# ------------------------------------------------------- run.py end to end

def _last_line(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1]) if out else None


def test_without_a_tpu_it_fails_and_prints_no_result(run_py, capsys):
    assert run_py.main(["--workload", "terasort.w1", "--seed", "1",
                        "--seconds", "0.1", "--trace", "0"]) != 0
    captured = capsys.readouterr()
    assert captured.out == "" and "no TPU" in captured.err


def test_without_the_program_it_fails_and_prints_no_result(tmp_path):
    """A directory that holds only BENCHMARK.json and the files under
    ``paths``."""
    shutil.copy(os.path.join(_ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(_BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "terasort.w1",
         "--seed", "1", "--seconds", "0.1", "--trace", "0", "--rehearse"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout == ""
    assert "not here" in p.stderr


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", _cells())
def test_rehearsal_is_correct_and_never_prints_the_result_line(
        run_py, rehearsal_env, capsys, cell, trace):
    assert run_py.main(["--workload", cell, "--seed", str(2**31 + 5),
                        "--seconds", "0.05", "--trace", str(trace),
                        "--rehearse"]) == 0
    last = _last_line(capsys)
    assert last["rehearsal"] is True and "metrics" not in last
    assert last["correct"] is True and last["failed"] == 0
    assert last["check"]["jobs_compared"] >= 1
    assert all(v["value"] <= v["limit"] for k, v in last["check"].items()
               if k != "jobs_compared")
    if trace:
        assert last["attempted"] == 3
        # a CPU has no device plane: every trace reader stays silent
        assert "device_idle_share" not in last["reported"]
        assert "dispatches_per_job" in last["reported"]
    else:
        assert {"records_per_s", "job_s_slowest", "setup_s"} \
            <= set(last["reported"])


@pytest.mark.parametrize("cell", _cells())
def test_the_control_comes_out_not_correct(run_py, rehearsal_env, capsys,
                                           cell):
    assert run_py.main(["--workload", cell, "--seed", "11", "--seconds",
                        "0.05", "--trace", "0", "--rehearse",
                        "--control"]) == 0
    last = _last_line(capsys)
    assert last["correct"] is False and last["failed"] == 0


def _fault_unchanged(job, monkeypatch):
    """A step that returns its state unchanged: the input comes back."""
    seen = {}
    real = job.pipeline
    monkeypatch.setattr(job, "pipeline", lambda ctx, inp: (
        seen.update(inp=inp), real(ctx, inp))[1])
    monkeypatch.setattr(job, "fetch", lambda h: dict(seen["inp"]))


def _fault_half(job, monkeypatch):
    """Half of the batch left out."""
    real = job.pipeline
    monkeypatch.setattr(job, "pipeline", lambda ctx, inp: real(
        ctx, {k: v[:len(v) // 2] for k, v in inp.items()}))


def _fault_no_exchange(job, monkeypatch):
    """The exchange between chips left out: every quarter of the input
    is processed where it lies and the parts are never merged."""
    real_p, real_f, real_d = job.pipeline, job.fetch, job.dispose

    def pipeline(ctx, inp):
        n = len(next(iter(inp.values())))
        q = max(1, n // 4)
        return [real_p(ctx, {k: v[i:i + q] for k, v in inp.items()})
                for i in range(0, n, q)]

    def fetch(handles):
        parts = [real_f(h) for h in handles]
        return {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}

    monkeypatch.setattr(job, "pipeline", pipeline)
    monkeypatch.setattr(job, "fetch", fetch)
    monkeypatch.setattr(job, "dispose",
                        lambda hs: [real_d(h) for h in hs])


def _fault_altered(job, monkeypatch):
    """One answer altered where it is produced: the last column's last
    element of one row."""
    real = job.fetch

    def fetch(handle):
        got = {k: np.array(v) for k, v in real(handle).items()}
        last = got[sorted(got)[-1]] if "c" not in got else got["c"]
        last[len(last) // 2, ...] += 1
        return got

    monkeypatch.setattr(job, "fetch", fetch)


def _faults():
    """Each fault in each cell that can have it: one chip has no exchange
    to leave out."""
    with open(os.path.join(_ROOT, "BENCHMARK.json")) as f:
        cells = json.load(f)["workloads"]
    return [pytest.param(c["name"], fault,
                         id=f"{c['name']}-{fault.__name__[7:]}")
            for c in cells
            for fault in (_fault_unchanged, _fault_half, _fault_no_exchange,
                          _fault_altered)
            if fault is not _fault_no_exchange or c["chips"] > 1]


@pytest.mark.parametrize("cell,fault", _faults())
def test_a_broken_timed_path_comes_out_not_correct(
        run_py, bench, rehearsal_env, monkeypatch, capsys, cell, fault):
    job, _ = _job(run_py, bench, cell)
    fault(job, monkeypatch)
    assert run_py.main(["--workload", cell, "--seed", "13", "--seconds",
                        "0.05", "--trace", "0", "--rehearse"]) == 0
    last = _last_line(capsys)
    assert last["correct"] is False
    assert any(v["value"] > v["limit"] for k, v in last["check"].items()
               if k != "jobs_compared")
