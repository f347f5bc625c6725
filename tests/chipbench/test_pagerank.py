"""Job kind ``pagerank`` and the iteration layer's three readers, on the
CPU: the generator against the stated R-MAT shape, the byte count by
hand, the reference against itself, its binary32 control and a
nine-iteration result, the readers on a made-up ring, and a traced
rehearsal of ``pagerank.w1``. No number here is a device number."""

import importlib.util
import json
import os
import sys

import numpy as np
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_BENCH = os.path.join(_ROOT, "chipbench")
TRAFFIC = {"graph500_scale": 10, "edge_factor": 16, "iterations": 10,
           "damping": 0.85}
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
    return _load(os.path.join(_BENCH, "jobs", "pagerank.py"),
                 "pagerank_job_t")


def reader(name):
    return _load(os.path.join(_BENCH, "layer_metrics", name + ".py"),
                 "reader_" + name)


@pytest.fixture(scope="module")
def graph(job):
    inp = job.generate(2**31 + 28, TRAFFIC, {})
    return inp, job.reference(inp, TRAFFIC)


# ------------------------------------------------------------- generator

def test_the_generator_is_a_function_of_the_seed_alone(job):
    big = 2**31 + 11
    a, b = (job.generate(big, TRAFFIC, {}) for _ in range(2))
    other = job.generate(big + 1, TRAFFIC, {})
    assert sorted(a) == ["d", "s"]
    for k in a:
        assert a[k].dtype == np.int64
        assert a[k].tobytes() == b[k].tobytes()
    assert any(a[k].tobytes() != other[k].tobytes() for k in a)


def test_the_graph_has_the_stated_rmat_shape(job, graph):
    inp, _ = graph
    n, m = job.pages(TRAFFIC), job.edges(TRAFFIC)
    assert (n, m) == (1024, 16 * 1024)
    assert job.records(TRAFFIC) == 10 * m
    for k in ("s", "d"):
        assert inp[k].shape == (m,)
        assert inp[k].min() >= 0 and inp[k].max() < n
    # one quadrant per bit: the hottest target takes (A + C)^scale of
    # the edges and the hottest source (A + B)^scale, 0.76^10 = 6.4 %,
    # where a uniform graph would give each page 0.1 %
    for k in ("s", "d"):
        hottest = np.bincount(inp[k], minlength=n).max() / m
        assert 0.5 * 0.76 ** 10 < hottest < 2 * 0.76 ** 10
    # labels are permuted: the hot vertex is not page 0 for every seed
    hot = {int(np.bincount(job.generate(s, TRAFFIC, {})["d"]).argmax())
           for s in range(4)}
    assert len(hot) > 1
    # multi-edges and self-loops are left in
    assert np.count_nonzero(inp["s"] == inp["d"]) > 0
    pairs = inp["s"] * n + inp["d"]
    assert len(np.unique(pairs)) < m


def test_min_bytes_by_hand(job):
    # 10 x (16,384 x 24 + 2 x 8 x 1,024) + 16 x 16,384 + 8 x 1,024
    assert job.min_bytes(TRAFFIC, {}, None) == 10 * (393_216 + 16_384) \
        + 262_144 + 8_192 == 4_366_336
    assert job.gather_bytes(16_384) == 393_216
    assert job.table_bytes(1_024) == 16_384


# ---------------------------------------------------- reference, compare

def test_the_reference_is_a_distribution_short_of_the_dangling_rank(
        job, graph):
    inp, want = graph
    r = want["r"]
    assert r.dtype == np.float64 and r.shape == (1024,)
    assert np.all(r >= 0.15 / 1024)
    # no redistribution of dangling pages' rank: the sum falls below 1
    assert 0.2 < r.sum() < 1.0


def test_reference_passes_itself_and_fails_its_controls(job, graph):
    inp, want = graph
    ok = job.compare(want, want)
    assert ok == {"ranks_missing": (0, 0),
                  "rank_rel_err_max": (0.0, job.RANK_REL_ERR_LIMIT)}
    # binary32 ranks between the iterations
    ctl = job.compare(job.control(inp, TRAFFIC), want)
    assert ctl["ranks_missing"] == (0, 0)
    assert 1e-9 < ctl["rank_rel_err_max"][0] < 1e-5
    # nine iterations in place of ten
    nine = job.reference(inp, dict(TRAFFIC, iterations=9))
    assert job.compare(nine, want)["rank_rel_err_max"][0] > 1e-4
    # the same additions in another order stay far inside the limit
    order = np.random.default_rng(1).permutation(len(inp["s"]))
    shuffled = job.reference({k: v[order] for k, v in inp.items()},
                             TRAFFIC)
    assert job.compare(shuffled, want)["rank_rel_err_max"][0] < 1e-12


@pytest.mark.parametrize("got,missing", [
    ({"s": np.zeros(5, np.int64), "d": np.zeros(5, np.int64)}, 1024),
    (None, 1024),
    ({"r": np.zeros((1024, 1))}, 1024),
    ({"r": np.zeros(1000)}, 24),
    ({"r": np.zeros(1030)}, 6),
])
def test_a_result_without_the_ranks_is_ranks_missing(job, graph, got,
                                                     missing):
    assert job.compare(got, graph[1]) == {"ranks_missing": (missing, 0)}


def test_a_rank_that_is_not_a_number_is_not_correct(job, graph):
    _, want = graph
    bad = {"r": want["r"].copy()}
    bad["r"][7] = np.nan
    value, limit = job.compare(bad, want)["rank_rel_err_max"]
    assert np.isfinite(value) and value > limit
    json.dumps(value)


# ------------------------------------------------------ the three readers

class Ring:
    """Made-up span records of jobs that loop."""

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

    def job(self, pipe, t0, first):
        """One job of 1 s: a root ``Iterate`` stage of 0.9 s over, in the
        first job, a captured iteration (0.2 s, a 0.15 s dispatch inside)
        and a whole-loop replay of 9 iterations (0.5 s, a 0.45 s
        dispatch); in a later job a 0.05 s rebind (a 0.04 s dispatch)
        and two replays of one iteration each (0.3 s with 0.25 s of
        dispatch, 0.2 s with 0.18 s)."""
        root = self.add("stage", "Iterate", t0, 0.9, dia_id=pipe + 1,
                        pipe=pipe)
        if first:
            cap = self.add("loop", "capture", t0 + 0.01, 0.2, root,
                           iter=0, mode="capture")
            self.add("dispatch", "fused_Zip", t0 + 0.02, 0.15, cap)
            rep = self.add("loop", "replay", t0 + 0.3, 0.5, root, iter=1,
                           fori_iters=9)
            self.add("dispatch", "loop_fori", t0 + 0.31, 0.45, rep)
        else:
            reb = self.add("loop", "rebind", t0 + 0.01, 0.05, root)
            self.add("dispatch", "fused_Zip", t0 + 0.015, 0.04, reb)
            for k, (dur, inner) in enumerate(((0.3, 0.25), (0.2, 0.18))):
                rep = self.add("loop", "replay", t0 + 0.1 + 0.4 * k, dur,
                               root, iter=k)
                self.add("dispatch", "fused_Zip", t0 + 0.11 + 0.4 * k,
                         inner, rep)
        fin = self.add("stage", "AllGather", t0 + 0.92, 0.08,
                       dia_id=pipe + 2, pipe=pipe)
        self.add("wait", "device", t0 + 0.93, 0.05, fin)
        return root


def made_up_ring(loops=True):
    r = Ring()
    if loops:
        r.job(0, 10.0, first=True)      # the warm-up job: left out
        r.job(3, 11.5, first=True)
        r.job(6, 12.5, first=False)
    else:
        for pipe, t0 in ((0, 10.0), (3, 11.5), (6, 12.5)):
            root = r.add("stage", "Sort", t0, 0.9, dia_id=pipe, pipe=pipe)
            r.add("dispatch", "fused_Sort", t0 + 0.1, 0.5, root)
    return r.recs


# job 1: capture 0.2 - 0.15 and replay 0.5 - 0.45; job 2: rebind 0.05 -
# 0.04, replays 0.3 - 0.25 and 0.2 - 0.18; 11 of 12 iterations replayed
WANT = {"loop_host_s_per_job": (0.05 + 0.05 + 0.01 + 0.05 + 0.02) / 2,
        "loop_captures_in_window": 1,
        "iterations_replayed_share": 100.0 * 11 / 12}


@pytest.mark.parametrize("window_s", [2.0, None],
                         ids=["device_trace", "no_device_trace"])
@pytest.mark.parametrize("name", LOOP_METRICS)
def test_a_loop_reader_on_a_made_up_ring(monkeypatch, capsys, name,
                                         window_s):
    mod = reader(name)
    monkeypatch.setattr(mod.loop_window.span_window, "ring_records",
                        made_up_ring)
    run = {"jobs": 2, "trace": window_s and {"window_s": window_s}}
    assert mod.read(run) == pytest.approx(WANT[name], abs=1e-9)
    # computed once per run, kept on it; the six phases are printed with
    # the loop's self time beside the root stages' seconds
    assert mod.loop_window.CACHE_KEY in run
    assert mod.read(run) == pytest.approx(WANT[name], abs=1e-9)
    line = next(l for l in capsys.readouterr().err.splitlines()
                if l.startswith("six phases + loop self"))
    said = dict(kv.split("=") for kv in line.split(": ", 1)[1].split())
    assert float(said["sum"]) == pytest.approx(float(said["root_stage_s"]))


@pytest.mark.parametrize("name", LOOP_METRICS)
def test_a_loop_reader_with_nothing_to_read_returns_none(monkeypatch,
                                                         name):
    mod = reader(name)
    monkeypatch.setattr(mod.loop_window.span_window, "ring_records",
                        lambda: made_up_ring(loops=False))
    assert mod.read({"jobs": 2, "trace": None}) is None
    # no sound window: a program without the records, too few pipelines
    monkeypatch.setattr(mod.loop_window.span_window, "ring_records",
                        lambda: None)
    assert mod.read({"jobs": 2, "trace": None}) is None
    monkeypatch.setattr(mod.loop_window.span_window, "ring_records",
                        made_up_ring)
    assert mod.read({"jobs": 3, "trace": None}) is None
    assert mod.read({"jobs": 0, "trace": None}) is None


def test_a_replay_that_fell_back_ran_no_iteration():
    lw = reader("loop_host_s_per_job").loop_window
    r = Ring()
    root = r.add("stage", "Iterate", 1.0, 1.0, pipe=0)
    r.add("loop", "replay", 1.1, 0.1, root, iter=1, error="boom")
    r.add("loop", "capture", 1.3, 0.2, root, iter=1)
    r.add("loop", "replay", 1.6, 0.1, root, iter=2)
    totals = lw.sum_loops([r.recs])
    assert (totals["captures"], totals["iterations_replayed"],
            totals["iterations"]) == (1, 1, 2)
    assert totals["self_s"] == pytest.approx(0.4)


def test_benchmark_json_lists_the_loop_metrics_in_the_loop_cell_alone():
    """In the loop cells alone: the first loop cell first, and every cell
    listed runs a job kind whose ``pipeline`` calls ``Iterate`` (today
    ``pagerank`` and ``kmeans``; a later kind is told by its source)."""
    with open(os.path.join(_ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    files = {c["name"]: c["file"] for c in bench["configs"]}
    loop_kinds = set()
    for name in LOOP_METRICS:
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert entry["source"] == "program_span"
        assert entry["workloads"][0] == "pagerank.w1"
        assert entry["layer"] == "iteration (`api/loop.py`)"
        for listed in entry["workloads"]:
            with open(os.path.join(_ROOT,
                                   files[cells[listed]["config"]])) as f:
                kind = json.load(f)["job"]
            with open(os.path.join(_BENCH, "jobs", kind + ".py")) as f:
                assert " Iterate(" in f.read(), (listed, kind)
            loop_kinds.add(kind)
    assert loop_kinds >= {"pagerank", "kmeans"}
    cell = cells["pagerank.w1"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "pagerank-graph500", "closed_rmat_i10", 1)


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


def test_a_traced_rehearsal_reports_the_loop_metrics(rehearsal_env, capsys):
    run_py = _load(os.path.join(_BENCH, "run.py"), "chipbench_run_loops")
    assert run_py.main(["--workload", "pagerank.w1", "--seed",
                        str(2**31 + 28), "--seconds", "0.05", "--trace",
                        "1", "--rehearse"]) == 0
    captured = capsys.readouterr()
    last = json.loads(captured.out.strip().splitlines()[-1])
    assert last["correct"] is True and last["attempted"] == 3
    assert set(LOOP_METRICS) | set(SPAN_METRICS) <= set(last["reported"])

    def said(start):
        line = next(l for l in captured.err.splitlines()
                    if l.startswith(start))
        return dict(kv.split("=") for kv in line.split(": ", 1)[1].split())

    # every job of the window took over the warm-up job's tape: three
    # dispatches (the degrees, the first ranks, one whole-loop program)
    loops = said("loop spans over 3 jobs")
    assert (loops["captures"], loops["rebinds"]) == ("0", "3")
    assert loops["iterations_replayed"] == loops["iterations"] == "30"
    # the same programs in each of the three jobs, and a handful of them:
    # a tape replayed call by call reads 36 or more for 10 iterations a
    # job, an index plan dispatched on its own (ROADMAP D17) 12
    dispatches = last["counts"]["device_dispatches"]
    assert dispatches % 3 == 0 and 0 < dispatches <= 12
    assert last["counts"]["device_fetches"] == 3
    # the six phases and the loop's self time account for the root stages
    both = said("six phases + loop self")
    assert float(both["sum"]) == pytest.approx(float(both["root_stage_s"]),
                                               rel=0.01)
    phases = said("host phases per job")
    assert float(phases["upload_bytes"]) == 2 * 8 * 16 * 256
