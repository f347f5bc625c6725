"""Job kind ``kmeans`` and the ``index_plans_per_job`` reader, on the
CPU: the generator against the stated shape, the byte count by hand,
the reference against a brute-force Lloyd, its binary32 control, the
comparison on broken results, the reader on a made-up ring, and a
rehearsal of ``kmeans.w1`` traced and untraced. No number here is a
device number."""

import importlib.util
import json
import os
import sys

import numpy as np
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_BENCH = os.path.join(_ROOT, "chipbench")
TRAFFIC = {"points": 512, "dim": 3, "clusters": 10, "iterations": 10}
EVERY_CELL = ("dispatches_per_job", "fetches_per_job", "compiles_in_window",
              "device_idle_share", "device_busy_ms_per_job", "job_roofline",
              "hbm_peak_share", "oom_ladder_events")
LOOP_METRICS = ("loop_host_s_per_job", "loop_captures_in_window",
                "iterations_replayed_share")
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
def job():
    return _load(os.path.join(_BENCH, "jobs", "kmeans.py"), "kmeans_job_t")


@pytest.fixture(scope="module")
def reader():
    return _load(os.path.join(_BENCH, "layer_metrics",
                              "index_plans_per_job.py"),
                 "reader_index_plans_per_job")


@pytest.fixture(scope="module")
def cloud(job):
    inp = job.generate(2**31 + 32, TRAFFIC, {})
    return inp, job.reference(inp, TRAFFIC)


# ------------------------------------------------------------- generator

def test_the_generator_is_a_function_of_the_seed_alone(job):
    big = 2**31 + 11
    a, b = (job.generate(big, TRAFFIC, {}) for _ in range(2))
    other = job.generate(big + 1, TRAFFIC, {})
    assert sorted(a) == ["c0", "x"]
    for k in a:
        assert a[k].dtype == np.float64
        assert a[k].tobytes() == b[k].tobytes()
    assert any(a[k].tobytes() != other[k].tobytes() for k in a)


def test_the_points_have_the_stated_shape(job, cloud):
    inp, _ = cloud
    assert inp["x"].shape == (512, 3) and inp["c0"].shape == (10, 3)
    assert inp["x"].min() >= 0.0 and inp["x"].max() < job.COORD_RANGE
    # uniform: every octant of the cube holds about an eighth
    octant = (inp["x"] >= job.COORD_RANGE / 2) @ np.array([1, 2, 4])
    assert np.bincount(octant, minlength=8).min() > 512 / 8 / 2
    # the initial centroids are k distinct points of the set
    rows = {r.tobytes() for r in inp["x"]}
    assert len({r.tobytes() for r in inp["c0"]}) == 10
    assert all(r.tobytes() in rows for r in inp["c0"])
    assert job.records(TRAFFIC) == 512 * 10


def test_min_bytes_by_hand(job):
    # ten times (every 24-byte point, the ten centroids read and
    # written) and the upload read once
    assert job.min_bytes(TRAFFIC, {}, None) == \
        10 * (24 * 512 + 2 * 24 * 10) + 24 * 512
    cell = {"points": 4194304, "dim": 3, "clusters": 10, "iterations": 10}
    assert job.min_bytes(cell, {}, None) == 1_107_301_056


# ------------------------------------------------------------- reference

def test_the_reference_is_lloyd(job, cloud):
    inp, want = cloud
    c = inp["c0"].copy()
    for _ in range(10):
        lab = ((inp["x"][:, None] - c[None]) ** 2).sum(-1).argmin(1)
        c = np.stack([inp["x"][lab == j].mean(0) if (lab == j).any()
                      else c[j] for j in range(10)])
    assert np.abs(want["c"] - c).max() < 1e-12 * job.COORD_RANGE
    assert want["c"].shape == (10, 3) and want["c"].dtype == np.float64


def test_an_empty_cluster_keeps_its_centroid(job):
    x = np.array([[1.0, 1.0, 1.0], [2.0, 2.0, 2.0], [3.0, 3.0, 3.0]])
    c0 = np.array([[2.0, 2.0, 2.0], [900.0, 900.0, 900.0]])
    got = job.reference({"x": x, "c0": c0}, {"iterations": 3})
    assert np.array_equal(got["c"], [[2.0] * 3, [900.0] * 3])


def test_the_reference_works_in_blocks(job, cloud, monkeypatch):
    inp, want = cloud
    monkeypatch.setattr(job, "_BLOCK", 100)
    assert np.array_equal(job.reference(inp, TRAFFIC)["c"], want["c"])


def test_the_control_comes_out_not_correct(job):
    # at the rehearsal's size, where a centroid held in binary32 is off
    # by 1e-8 to 1e-7 of the range
    traffic = {**TRAFFIC, "points": 4096}
    inp = job.generate(11, traffic, {})
    want = job.reference(inp, traffic)
    good = job.compare(want, want)
    assert good == {"centers_missing": (0, 0),
                    "center_err_max": (0.0, job.CENTER_ERR_LIMIT)}
    bad = job.compare(job.control(inp, traffic), want)
    assert bad["centers_missing"] == (0, 0)
    value, limit = bad["center_err_max"]
    assert limit == 1e-9 and 1e-9 < value < 1e-6


# ------------------------------------------------------------ comparison

def test_compare_reads_a_broken_result_as_centers_missing(job, cloud):
    inp, want = cloud
    missing = {"centers_missing": (10, 0)}
    # the input in the result's place, halved as the harness's fault
    # halves it; no result; another number of centroids; flat
    assert job.compare({k: v[:len(v) // 2] for k, v in inp.items()},
                       want) == missing
    assert job.compare(dict(inp), want) == missing
    assert job.compare(None, want) == missing
    assert job.compare({"c": want["c"][:5]}, want) == missing
    assert job.compare({"c": want["c"].ravel()}, want) == missing


def test_compare_reads_errors_against_the_coordinate_range(job, cloud):
    _, want = cloud
    moved = want["c"].copy()
    moved[3, 1] += 2e-6
    value, limit = job.compare({"c": moved}, want)["center_err_max"]
    assert value == pytest.approx(2e-9, rel=1e-3) and value > limit
    moved[3, 1] = np.nan
    assert job.compare({"c": moved}, want)["center_err_max"][0] == \
        np.finfo(np.float64).max
    # nine iterations are not ten
    inp = cloud[0]
    nine = job.reference(inp, {**TRAFFIC, "iterations": 9})
    assert job.compare(nine, want)["center_err_max"][0] > 1e-6


# ----------------------------------------------------- the reader's ring

class Ring:
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


def made_up_ring(field=True):
    """Three jobs of one pipeline each. The warm-up job captures (a
    dispatch with one plan in place) and replays nine iterations in one
    whole-loop dispatch; the second rebinds and replays ten in one; the
    third replays call by call, two iterations of one dispatch each."""
    f = (lambda n: {"index_plans": n}) if field else (lambda n: {})
    r = Ring()
    for pipe, t0 in ((0, 10.0), (3, 11.5), (6, 12.5)):
        root = r.add("stage", "Iterate", t0, 0.9, dia_id=pipe, pipe=pipe)
        if pipe == 0:
            cap = r.add("loop", "capture", t0 + 0.01, 0.2, root, iter=0)
            r.add("dispatch", "fused_R2I", t0 + 0.02, 0.1, cap, **f(1))
            rep = r.add("loop", "replay", t0 + 0.3, 0.5, root, iter=1,
                        fori_iters=9, **f(9))
            r.add("dispatch", "loop_fori", t0 + 0.31, 0.4, rep, **f(0))
        elif pipe == 3:
            r.add("loop", "rebind", t0 + 0.01, 0.01, root)
            rep = r.add("loop", "replay", t0 + 0.1, 0.7, root, iter=0,
                        fori_iters=10, **f(10))
            r.add("dispatch", "loop_fori", t0 + 0.11, 0.6, rep, **f(0))
        else:
            for k in range(2):
                rep = r.add("loop", "replay", t0 + 0.1 + 0.3 * k, 0.2,
                            root, iter=k)
                r.add("dispatch", "fused_R2I", t0 + 0.11 + 0.3 * k, 0.1,
                      rep, **f(1))
                r.add("dispatch", "allgather_arrays", t0 + 0.25 + 0.3 * k,
                      0.01, rep, **f(0))
    return r.recs


@pytest.mark.parametrize("window_s", [2.0, None],
                         ids=["device_trace", "no_device_trace"])
def test_the_reader_counts_every_plan_once(reader, monkeypatch, capsys,
                                           window_s):
    monkeypatch.setattr(reader.span_window, "ring_records", made_up_ring)
    run = {"jobs": 2, "trace": window_s and {"window_s": window_s}}
    # the window's two jobs: 10 on the whole-loop replay span (0 on its
    # dispatch), then 1 + 1 on two dispatches (none on their replays)
    assert reader.read(run) == 6.0
    err = capsys.readouterr().err
    assert "loop spans over 2 jobs" in err
    assert "six phases + loop self" in err


def test_the_reader_with_nothing_to_read_returns_none(reader, monkeypatch):
    monkeypatch.setattr(reader.span_window, "ring_records",
                        lambda: made_up_ring(field=False))
    assert reader.read({"jobs": 2, "trace": None}) is None
    monkeypatch.setattr(reader.span_window, "ring_records", lambda: None)
    assert reader.read({"jobs": 2, "trace": None}) is None
    monkeypatch.setattr(reader.span_window, "ring_records", made_up_ring)
    assert reader.read({"jobs": 3, "trace": None}) is None
    assert reader.read({"jobs": 0, "trace": None}) is None


# ------------------------------------------------------ the data files

def test_benchmark_json_names_the_cell_and_its_metrics():
    """Every entry is found by its NAME: where it stands in a list is the
    driver's business (``test_append_only.py``), and later cells and
    metrics follow it."""
    with open(os.path.join(_ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == "kmeans.w1")
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("kmeans-uniform3d", "closed_uniform_k10_i10", 1)
    entry = next(c for c in bench["configs"]
                 if c["name"] == "kmeans-uniform3d")
    assert entry["reduced"] == ["points_per_job"]
    with open(os.path.join(_ROOT, entry["file"])) as f:
        config = json.load(f)
    assert config["job"] == "kmeans" and config["source"] == entry["source"]
    assert set(config["reduced"]) == set(entry["reduced"])
    assert (config["shapes"]["dimension"], config["shapes"]["clusters"],
            config["shapes"]["iterations"]) == (3, 10, 10)
    with open(os.path.join(_BENCH, "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    assert (traffic["dim"], traffic["clusters"], traffic["iterations"]) \
        == (3, 10, 10)
    assert traffic["points"] in (1 << 21, 1 << 22, 1 << 23)
    assert traffic["check"] == {"jobs": "all"}
    metric = next(m for m in bench["per_layer"]
                  if m["name"] == "index_plans_per_job")
    assert {k: v for k, v in metric.items() if k != "workloads"} == {
        "name": "index_plans_per_job", "unit": "count", "better": "lower",
        "source": "program_span", "layer": "DIA ops and fusion",
        "moves": "records_per_s"}
    assert "kmeans.w1" in metric["workloads"]
    reported = {m["name"] for m in bench["per_layer"]
                if "kmeans.w1" in m["workloads"]}
    assert reported >= set(EVERY_CELL) | {"index_plans_per_job"}


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


@pytest.mark.parametrize("trace", [0, 1])
def test_a_rehearsal_rebinds_the_tape_and_counts_ten_plans(
        rehearsal_env, reader, capsys, trace):
    run_py = _load(os.path.join(_BENCH, "run.py"),
                   f"chipbench_run_kmeans{trace}")
    assert run_py.main(["--workload", "kmeans.w1", "--seed",
                        str(2**31 + 32), "--seconds", "0.05", "--trace",
                        str(trace), "--rehearse"]) == 0
    captured = capsys.readouterr()
    last = json.loads(captured.out.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["check"]["centers_missing"] == {"value": 0, "limit": 0}
    assert last["check"]["center_err_max"]["value"] < 1e-12
    jobs = last["attempted"]
    # one upload of the points, one of the centroids, one whole-loop
    # dispatch; the 240 bytes leave through np.asarray, uncounted
    assert last["counts"]["device_dispatches"] == jobs
    assert last["counts"]["device_uploads"] == 2 * jobs
    assert last["counts"]["device_fetches"] == 0
    # ten plans a job, off the spans of the run that has just ended
    assert reader.read({"jobs": jobs, "trace": None}) == 10.0
    if not trace:
        return
    assert jobs == 3 and "index_plans_per_job" in last["reported"]
    # the host phases and the loop's metrics are in the line, each a number
    assert set(LOOP_METRICS) | set(SPAN_METRICS) <= set(last["reported"])

    def said(start):
        line = next(l for l in captured.err.splitlines()
                    if l.startswith(start))
        return dict(kv.split("=") for kv in line.split(": ", 1)[1].split())

    # every job of the window took over the warm-up job's tape and ran
    # its ten iterations in one whole-loop program
    loops = said("loop spans over 3 jobs")
    assert (loops["captures"], loops["rebinds"]) == ("0", "3")
    assert loops["iterations_replayed"] == loops["iterations"] == "30"
    # the six phases and the loop's self time account for the root stages
    both = said("six phases + loop self")
    assert float(both["sum"]) == pytest.approx(float(both["root_stage_s"]),
                                               rel=0.01)
    phases = said("host phases per job")
    assert float(phases["upload_bytes"]) == 24 * 4096 + 24 * 10
    assert "compile spans inside the window: 0" in captured.err
