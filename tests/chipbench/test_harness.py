"""The chip benchmark's own arithmetic, on the CPU: the trace reduction,
the byte counts, the generators and references, the data files against
the contract's rules, and ``run.py`` end to end in a rehearsal -- with the
control and with the timed path broken underneath, where ``correct`` has
to come out false. No number here is a device number."""

import importlib.util
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_BENCH = os.path.join(_ROOT, "chipbench")


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# the rules on the data files, as functions of a root directory:
# test_append_only.py holds a copy with entries added to the same code
contract = _load(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "bench_contract.py"),
                 "chipbench_bench_contract")


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
    contract.check_data_files(_ROOT, bench)


def test_names_units_and_keys_are_within_the_contract(bench):
    contract.check_names_units_and_keys(bench)


def test_every_per_layer_metric_has_a_reader_and_moves_a_reported_metric(
        bench):
    contract.check_readers(_ROOT, bench)


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
           "jobs": 3, "job_seconds": [1.0, 2.5, 1.5], "compiles": 0,
           "cell": {"chips": 4},
           "peaks": {"hbm_bytes_per_s": 800e9}, "min_bytes": 3.2e9}
    want = {"dispatches_per_job": 3, "fetches_per_job": 2,
            "compiles_in_window": 0, "device_idle_share": 25.0,
            "device_busy_ms_per_job": 2000.0,
            # 0.8e9 B per chip / 800e9 B/s = 1 ms of 2,000 ms
            "job_roofline": 0.05, "hbm_peak_share": 50.0,
            "oom_ladder_events": 3, "collective_share": 5.0,
            "exchange_bytes_per_job": 1000, "job_s_max": 2.5}
    for name, value in want.items():
        read = run_py.load_module("layer_metrics", name).read
        assert read(run) == pytest.approx(value), name
    blind = dict(run, trace=None, memory=[{}], min_bytes=None,
                 job_seconds=[], stats=dict(run["stats"], exchanges=0))
    for name in ("device_idle_share", "device_busy_ms_per_job",
                 "job_roofline", "collective_share", "hbm_peak_share",
                 "exchange_bytes_per_job", "job_s_max"):
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
        assert {"dispatches_per_job", "job_s_max"} <= set(last["reported"])
    else:
        # whatever BENCHMARK.json lists end to end for the cell, no more
        bench = run_py.load_json("BENCHMARK.json")
        assert set(last["reported"]) == {
            m["name"] for m in run_py.metrics_of(bench, "end_to_end", cell)}
        assert {"records_per_s", "setup_s"} <= set(last["reported"])


def test_readers_get_every_counter_and_a_run_prints_ten(
        run_py, rehearsal_env, monkeypatch, capsys):
    """``run["stats"]`` holds whatever ``overall_stats()`` counts, by the
    program's own names, so a counter that a later PR adds reaches its
    reader with no edit here; what a run PRINTS stays the ten."""
    seen = {}
    reader = run_py.load_module("layer_metrics", "dispatches_per_job")
    real = reader.read
    monkeypatch.setattr(reader, "read",
                        lambda run: (seen.update(run), real(run))[1])
    assert run_py.main(["--workload", "pagerank.w1", "--seed",
                        str(2**31 + 35), "--seconds", "0.05", "--trace",
                        "1", "--rehearse"]) == 0
    captured = capsys.readouterr()
    last = json.loads(captured.out.strip().splitlines()[-1])
    assert last["correct"] is True and last["attempted"] == 3
    assert tuple(last["counts"]) == run_py.STAT_KEYS
    assert len(run_py.STAT_KEYS) == 10
    line = next(l for l in captured.err.splitlines()
                if l.startswith("stats over the window: "))
    assert [kv.split("=")[0] for kv in line.split(": ", 1)[1].split()] \
        == list(run_py.STAT_KEYS)
    stats = seen["stats"]
    assert all(stats[k] == last["counts"][k] for k in run_py.STAT_KEYS)
    # one plan for the degrees, one hoisted ahead of the loop (PR 29)
    assert stats["r2i_index_plans"] == 2 * seen["jobs"] == 6
    assert stats["upload_bytes"] > 0 and stats["device_uploads"] > 0
    # a gauge reads its change, a label is left out
    assert stats["workers"] == 0
    assert all(isinstance(v, (int, float)) for v in stats.values())


def test_stat_deltas_leave_out_what_one_end_lacks(run_py):
    before = {"a": 1, "gone": 2, "label": "x", "flag": True, "s": 0.5}
    after = {"a": 4, "new": 7, "label": "y", "flag": False, "s": 2.0}
    assert run_py.stat_deltas(before, after) == {"a": 3, "s": 1.5}


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
