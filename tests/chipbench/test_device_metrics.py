"""The readers of the program's ``transfer`` and ``device`` records (ISSUE
38): ``transfer_s_per_job`` and ``device_idle_s_per_job`` on made-up
records, what they return where there is nothing sound to read, and a
traced rehearsal of every cell that lists them. No number here is a
device number."""

import importlib.util
import json
import os
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_BENCH = os.path.join(_ROOT, "chipbench")
METRICS = ("transfer_s_per_job", "device_idle_s_per_job")


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name):
    return _load(os.path.join(_BENCH, "layer_metrics", name + ".py"),
                 "device_reader_" + name)


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

    def job(self, pipe, t0, device=True):
        """A job of two pulls, 1.3 s: the first sorts (two overlapping
        transfers 0.1-0.4, the program on the device 0.4-0.85, a wait
        and a fetch), the second, after 0.1 s in the caller, runs one
        small program 1.15-1.25."""
        root = self.add("stage", "Sort", t0, 1.0, pipe=pipe, dia_id=pipe)
        dist = self.add("stage", "Distribute", t0 + 0.01, 0.3, root,
                        pipe=pipe, dia_id=pipe + 1)
        for start, nbytes, moving in ((0.1, 1000, 0.2), (0.15, 3000, 0.25)):
            up = self.add("upload", "put", t0 + start, 0.05, dist,
                          bytes=nbytes, shape=[1, nbytes], dtype="uint8")
            self.add("transfer", "put", t0 + start, moving, up,
                     bytes=nbytes, shape=[1, nbytes], dtype="uint8")
        fus = self.add("fusion", "Sort", t0 + 0.32, 0.4, root)
        disp = self.add("dispatch", "fused_Sort", t0 + 0.35, 0.3, fus)
        if device:
            self.add("device", "fused_Sort", t0 + 0.4, 0.45, disp)
        self.add("wait", "device", t0 + 0.73, 0.15, root)
        self.add("fetch", "fetch", t0 + 0.88, 0.05, root, bytes=4)
        pull = self.add("stage", "MinMax", t0 + 1.1, 0.2, pipe=pipe,
                        dia_id=pipe)
        disp = self.add("dispatch", "reduce", t0 + 1.12, 0.01, pull)
        if device:
            self.add("device", "reduce", t0 + 1.15, 0.1, disp)


def three_jobs(device=True):
    """A warm-up job, a window of two, and after the window the
    harness's fetches of the kept results."""
    r = Records()
    for pipe, t0 in ((0, 10.0), (2, 11.5), (4, 13.0)):
        r.job(pipe, t0, device)
    for pipe, t0 in ((2, 15.0), (4, 15.6)):
        root = r.add("stage", "AllGatherArrays", t0, 0.5, pipe=pipe)
        r.add("wait", "device", t0 + 0.01, 0.1, root)
    return r.recs


RUN = {"jobs": 2, "trace": {"window_s": 3.0}}
CAUSES = {"transfer": 0.3, "caller": 0.1, "stage:MinMax": 0.09,
          "stage:Sort": 0.08, "stage:Distribute": 0.09, "fetch:fetch": 0.05,
          "wait:device": 0.03, "dispatch:reduce": 0.01}


def test_transfer_seconds_are_the_union_of_a_jobs_transfers(
        monkeypatch, capsys):
    mod = reader("transfer_s_per_job")
    monkeypatch.setattr(mod.span_window, "ring_records", three_jobs)
    # 0.1-0.3 and 0.15-0.4 overlap: 0.3 s, not 0.45
    assert mod.read(dict(RUN)) == pytest.approx(0.3)
    err = capsys.readouterr().err
    assert "uint8[1, 3000]=0.000(3000B,0.250000s)" in err
    assert "transfer / upload spans per job: 2/2 2/2" in err


@pytest.mark.parametrize("job_s", [None, 1.4])
def test_device_idle_by_cause(monkeypatch, capsys, job_s):
    """The job's seconds on the harness's clock, where the run has them,
    add what lies outside its records to ``caller``: the trace's job
    span takes in the caller's time before the first record and after
    the last."""
    mod = reader("device_idle_s_per_job")
    monkeypatch.setattr(mod.span_window, "ring_records", three_jobs)
    run = dict(RUN) if job_s is None else dict(RUN, job_seconds=[job_s] * 2)
    outside = 0.0 if job_s is None else job_s - 1.3
    # 1.3 s of records a job, 0.45 + 0.1 of it on the device
    assert mod.read(run) == pytest.approx(0.75 + outside)
    jobs = mod.span_window.window_jobs(three_jobs(), 2, 3.0)
    for job in jobs:
        got = mod.idle_by_cause(job, job_s)
        assert got == pytest.approx(dict(CAUSES,
                                         caller=CAUSES["caller"] + outside))
    err = capsys.readouterr().err
    assert "device seconds per job by program: fused_Sort=0.450000 " \
        "reduce=0.100000 total=0.550000" in err
    assert "device / dispatch spans per job: 2/2 2/2; closed at a " \
        "consumer's ready (donated): 0" in err
    line = next(l for l in err.splitlines() if "by cause" in l)
    assert line.split(": ", 1)[1].split()[0] == "transfer=0.300000"


@pytest.mark.parametrize("name", METRICS)
@pytest.mark.parametrize("case", ["wrapped", "tracer_off", "parent",
                                  "no_jobs"])
def test_where_nothing_sound_can_be_read_a_reader_returns_none(
        name, case):
    from thrill_tpu.common import trace
    mod = reader(name)
    run = dict(RUN)
    tracer = trace.Tracer(ring=256, enabled=case != "tracer_off")
    for rec in three_jobs(device=case != "parent"):
        if case != "parent" or rec["cat"] != "transfer":
            tracer._record(dict(rec))
    if case == "wrapped":
        for i in range(256):
            tracer.instant("plan", str(i))
    elif case == "no_jobs":
        run["jobs"] = 0
    assert mod.read(run) is None
    if case == "tracer_off":
        # the same records on a live Tracer read
        live = trace.Tracer(ring=256, enabled=True)
        for rec in three_jobs():
            live._record(dict(rec))
        assert mod.read(dict(RUN)) is not None


@pytest.mark.parametrize("name", METRICS)
def test_benchmark_json_lists_the_readers_in_the_six_cells(name):
    b = bench()
    entry = next(m for m in b["per_layer"] if m["name"] == name)
    assert entry["source"] == "program_span" and entry["better"] == "lower"
    assert entry["moves"] == "records_per_s"
    assert entry["workloads"][:6] == [w["name"] for w in b["workloads"]][:6]


@pytest.fixture
def rehearsal_env(monkeypatch):
    # what run.py sets for a rehearsal is restored afterwards: the worker
    # goes on to other tests
    for var in ("THRILL_TPU_HOST_RADIX", "THRILL_TPU_SORT_U32",
                "THRILL_TPU_PACK_MOVE", "THRILL_TPU_TRACE_RING"):
        monkeypatch.setenv(var, "")
        monkeypatch.delenv(var)
    monkeypatch.setattr(sys, "path", list(sys.path))


def said_pairs(err, prefix):
    line = next(l for l in err.splitlines() if l.startswith(prefix))
    return [tuple(map(int, p.split("/")))
            for p in line.split(": ", 1)[1].split(";")[0].split()]


@pytest.mark.parametrize("cell", next(
    m for m in bench()["per_layer"]
    if m["name"] == "device_idle_s_per_job")["workloads"])
def test_a_traced_rehearsal_reports_both(rehearsal_env, capsys, cell):
    run_py = _load(os.path.join(_BENCH, "run.py"), "chipbench_run_device")
    assert run_py.main(["--workload", cell, "--seed", str(2**31 + 38),
                        "--seconds", "0.05", "--trace", "1",
                        "--rehearse"]) == 0
    captured = capsys.readouterr()
    last = json.loads(captured.out.strip().splitlines()[-1])
    assert last["correct"] is True
    assert set(METRICS) <= set(last["reported"])
    # one record per dispatch and per upload, in every job of the window
    for prefix in ("device / dispatch spans per job",
                   "transfer / upload spans per job"):
        pairs = said_pairs(captured.err, prefix)
        assert pairs and all(a == b > 0 for a, b in pairs), pairs
