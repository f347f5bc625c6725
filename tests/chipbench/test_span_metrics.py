"""The ``program_span`` readers (PR 26) on the CPU: the window rule and
each reader's arithmetic on made-up records, what they return where there
is nothing sound to read, and ``run.py --rehearse --trace 1`` reporting
them. No number here is a device number."""

import importlib.util
import json
import os
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_BENCH = os.path.join(_ROOT, "chipbench")
SPAN_METRICS = ("upload_s_per_job", "upload_bytes_per_job",
                "dispatch_call_s_per_job", "sync_wait_s_per_job",
                "fetch_s_per_job", "host_plan_s_per_job",
                "compile_s_in_window")


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def span_window():
    return _load(os.path.join(_BENCH, "span_window.py"), "span_window_t")


def reader(name):
    return _load(os.path.join(_BENCH, "layer_metrics", name + ".py"),
                 "reader_" + name)


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(_ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


class Records:
    """Made-up span records: ``add`` returns the span's id."""

    def __init__(self):
        self.recs = []

    def add(self, cat, name, t0, dur_s, parent=None, **attrs):
        rec = {"event": "span", "cat": cat, "name": name,
               "span": len(self.recs) + 1, "t0_s": t0,
               "dur_us": int(round(dur_s * 1e6)), **attrs}
        if parent is not None:
            rec["parent"] = parent
        self.recs.append(rec)
        return rec["span"]

    def job(self, pipe, t0, compile_s=0.0, fetch=True):
        """One job of 1 s at ``t0``: stage > Distribute (0.3 s, of it
        0.2 s upload of 1,000 bytes) + fusion (0.4 s, of it a 0.3 s
        dispatch with ``compile_s`` of compile inside) + a 0.2 s wait
        and a 0.05 s fetch."""
        root = self.add("stage", "Sort", t0, 1.0, dia_id=pipe + 1,
                        pipe=pipe)
        dist = self.add("stage", "Distribute", t0 + 0.01, 0.3, root,
                        dia_id=pipe, pipe=pipe)
        self.add("upload", "put", t0 + 0.1, 0.2, dist, bytes=1000)
        fus = self.add("fusion", "Sort", t0 + 0.32, 0.4, root)
        self.recs.append({"event": "span", "kind": "instant",
                          "cat": "plan", "name": "fusion", "dur_us": 0,
                          "span": len(self.recs) + 1, "parent": fus})
        disp = self.add("dispatch", "fused_Sort", t0 + 0.35, 0.3, fus)
        if compile_s:
            self.add("compile", "fused_Sort", t0 + 0.36, compile_s, disp)
        if fetch:
            self.add("wait", "device", t0 + 0.73, 0.2, root)
            self.add("fetch", "fetch", t0 + 0.93, 0.05, root, bytes=4)
        return root


def three_jobs(compile_in_warmup=0.25, compile_in_window=0.0):
    """A warm-up job, a window of two, and after the window the
    harness's fetches of the kept results (first and last job)."""
    r = Records()
    r.job(0, 10.0, compile_s=compile_in_warmup)
    r.job(2, 11.5, compile_s=compile_in_window)
    r.job(4, 12.5)
    for pipe, t0 in ((2, 14.0), (4, 14.6)):
        root = r.add("stage", "AllGatherArrays", t0, 0.5, dia_id=pipe + 1,
                     pipe=pipe)
        r.add("wait", "device", t0 + 0.01, 0.1, root)
        r.add("fetch", "fetch", t0 + 0.11, 0.3, root, bytes=10 ** 6)
    return r.recs


WANT = {"upload_s_per_job": 0.2, "upload_bytes_per_job": 1000,
        "dispatch_call_s_per_job": 0.3, "sync_wait_s_per_job": 0.2,
        "fetch_s_per_job": 0.05,
        # 1.0 - 0.3 - 0.4 - 0.2 - 0.05 (root) + 0.1 (Distribute) + 0.1 (fusion)
        "host_plan_s_per_job": 0.25, "compile_s_in_window": 0.0}


@pytest.mark.parametrize("window_s", [2.0, None],
                         ids=["device_trace", "no_device_trace"])
@pytest.mark.parametrize("name", SPAN_METRICS)
def test_a_reader_leaves_out_the_warm_up_and_what_follows_the_window(
        monkeypatch, name, window_s):
    mod = reader(name)
    monkeypatch.setattr(mod.span_window, "ring_records", three_jobs)
    run = {"jobs": 2, "trace": window_s and {"window_s": window_s}}
    assert mod.read(run) == pytest.approx(WANT[name], abs=1e-9)
    # computed once per run, kept on it
    assert mod.span_window.CACHE_KEY in run
    assert mod.read(run) == pytest.approx(WANT[name], abs=1e-9)


def test_a_compile_inside_the_window_is_taken_out_of_the_dispatch(
        monkeypatch, capsys):
    compile_s, dispatch_s = (reader(n) for n in (
        "compile_s_in_window", "dispatch_call_s_per_job"))
    for mod in (compile_s, dispatch_s):
        monkeypatch.setattr(mod.span_window, "ring_records",
                            lambda: three_jobs(compile_in_window=0.2))
    run = {"jobs": 2, "trace": {"window_s": 2.0}}
    assert compile_s.read(run) == pytest.approx(0.2)
    assert "compile spans inside the window: 1 (fused_Sort)" \
        in capsys.readouterr().err
    # (0.3 - 0.2 + 0.3) / 2; the six still sum to the roots
    assert dispatch_s.read(dict(run)) == pytest.approx(0.2)
    p = dispatch_s.span_window.phases(dict(run))
    assert sum(p[k] for k in (
        "upload_s", "dispatch_call_s", "sync_wait_s", "fetch_s",
        "host_plan_s", "compile_s")) == pytest.approx(p["root_stage_s"])
    assert p["dispatch_spans"] == 2 and p["fetch_spans"] == 2


def test_the_window_rule(span_window):
    recs = three_jobs()
    jobs = span_window.window_jobs(recs, 2, 2.0)
    assert [len(j) for j in jobs] == [7, 7]
    assert all(r["t0_s"] < 13.5 for j in jobs for r in j)
    assert {r["cat"] for r in jobs[0]} == {
        "stage", "upload", "fusion", "dispatch", "wait", "fetch"}
    # a longer window takes the last job's result fetch in, which is why
    # the limit is the traced window's own length
    late = span_window.window_jobs(recs, 2, 3.2)
    assert [len(j) for j in late] == [7, 10]
    # fewer pipelines than the window's jobs and the warm-up: no guess
    assert span_window.window_jobs(recs, 3, 2.0) is None
    assert span_window.window_jobs([], 1, 1.0) is None
    # spans under no stage belong to no pipeline
    orphan = {"event": "span", "cat": "dispatch", "name": "x", "span": 99,
              "t0_s": 11.6, "dur_us": 5}
    assert [len(j) for j in span_window.window_jobs(
        recs + [orphan], 2, 2.0)] == [7, 7]


def test_self_seconds_by_span_name_the_distribute_stage(span_window):
    p = span_window.sum_phases(span_window.window_jobs(three_jobs(), 2, 2.0))
    assert p["self_s_by_span"] == pytest.approx(
        {"stage:Sort": 0.1, "stage:Distribute": 0.2, "fusion:Sort": 0.2})


@pytest.mark.parametrize("case", ["wrapped", "tracer_off", "no_ring",
                                  "parent_without_latest", "no_jobs"])
def test_where_nothing_sound_can_be_read_a_reader_returns_none(
        monkeypatch, case):
    from thrill_tpu.common import trace
    mod = reader("upload_s_per_job")
    run = {"jobs": 2, "trace": None}
    tracer = trace.Tracer(ring=0 if case == "no_ring" else 64,
                          enabled=case != "tracer_off")
    for rec in three_jobs():
        tracer._record(dict(rec))
    if case == "wrapped":
        for i in range(64):
            tracer.instant("plan", str(i))
    elif case == "parent_without_latest":
        monkeypatch.delattr(trace, "latest")
    elif case == "no_jobs":
        run["jobs"] = 0
    assert mod.read(run) is None
    if case == "tracer_off":
        # the same records on a live Tracer read
        live = trace.Tracer(ring=64, enabled=True)
        for rec in three_jobs():
            live._record(dict(rec))
        assert mod.read({"jobs": 2, "trace": None}) == pytest.approx(0.2)


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_benchmark_json_lists_the_reader_in_the_three_cells(bench, name):
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert entry["source"] == "program_span" and entry["better"] == "lower"
    # the three cells of PR 26 first: later cells are appended, not put in
    assert entry["workloads"][:3] == ["terasort.w1", "wordcount.w1",
                                      "terasort.w4"]
    cells = {w["name"] for w in bench["workloads"]}
    assert set(entry["workloads"]) <= cells
    assert os.path.exists(os.path.join(_BENCH, "layer_metrics",
                                       name + ".py"))


@pytest.fixture
def rehearsal_env(monkeypatch):
    # what run.py sets for a rehearsal is restored afterwards: the worker
    # goes on to other tests
    for var in ("THRILL_TPU_HOST_RADIX", "THRILL_TPU_SORT_U32",
                "THRILL_TPU_PACK_MOVE"):
        monkeypatch.setenv(var, "")
        monkeypatch.delenv(var)
    monkeypatch.setattr(sys, "path", list(sys.path))


@pytest.mark.parametrize("cell", ["terasort.w1", "wordcount.w1",
                                  "terasort.w4"])
def test_a_traced_rehearsal_reports_the_span_metrics(rehearsal_env, capsys,
                                                     cell):
    run_py = _load(os.path.join(_BENCH, "run.py"), "chipbench_run_spans")
    assert run_py.main(["--workload", cell, "--seed", str(2**31 + 26),
                        "--seconds", "0.05", "--trace", "1",
                        "--rehearse"]) == 0
    captured = capsys.readouterr()
    last = json.loads(captured.out.strip().splitlines()[-1])
    assert last["correct"] is True
    assert set(SPAN_METRICS) <= set(last["reported"])
    # the program's spans and its counters agree, per job
    line = next(l for l in captured.err.splitlines()
                if l.startswith("host phases per job"))
    said = dict(kv.split("=") for kv in line.split(": ", 1)[1].split())
    jobs = last["attempted"]
    assert float(said["dispatch_spans"]) \
        == last["counts"]["device_dispatches"] / jobs
    assert float(said["fetch_spans"]) \
        == last["counts"]["device_fetches"] / jobs
    assert float(said["sum"]) == pytest.approx(float(said["root_stage_s"]),
                                               rel=0.02)
