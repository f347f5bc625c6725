"""A loop whose carry is a pytree of device arrays (api/loop.py): it
joins the pipeline of its first invariant DIA, a later call of the same
body takes over the kept tape, and every ``dispatch`` span (every
``loop`` / ``replay`` span of a whole-loop dispatch) says how many
``ReduceToIndex`` index plans its program computed. The loop is the chip
benchmark's k-means job (``chipbench/jobs/kmeans.py``: a ``Bind`` of
the carry inside a ``Map``, a fold whose index is computed from the
carry, an ``AllGatherArrays`` closing the iteration), against that job
kind's own numpy reference."""

import importlib.util
import os

import numpy as np
import pytest

from thrill_tpu.api import RunLocalMock
from thrill_tpu.api.ops.reduce import DENSE_FOLD_ROWS

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TRAFFIC = {"points": 1024, "dim": 3, "clusters": 10, "iterations": 10}
STATS = ("loop_plan_builds", "loop_plan_rebinds", "loop_fori_iters",
         "loop_replay_fallbacks", "device_dispatches", "r2i_index_plans",
         "r2i_dense_plans")


def _load(*parts):
    spec = importlib.util.spec_from_file_location(
        parts[-1] + "_tree_t", os.path.join(_ROOT, *parts) + ".py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    for var in ("THRILL_TPU_LOOP_REPLAY", "THRILL_TPU_LOOP_FORI",
                "THRILL_TPU_FUSE", "THRILL_TPU_TRACE",
                "THRILL_TPU_TRACE_RING"):
        monkeypatch.delenv(var, raising=False)


def three_jobs(workers, job_kind=None):
    """A warm-up job and two more, each on a point set of its own; the
    results, the finished run's span records and the counter deltas
    per job."""
    km = job_kind or _load("chipbench", "jobs", "kmeans")
    out = {"stats": [], "got": [], "want": [], "km": km}

    def job(ctx):
        for seed in (2**31 + 1, 2, 3):
            inp = km.generate(seed, TRAFFIC, {})
            s0 = ctx.overall_stats()
            out["got"].append(km.pipeline(ctx, inp)["c"])
            s1 = ctx.overall_stats()
            out["want"].append(km.reference(inp, TRAFFIC)["c"])
            out["stats"].append({k: s1[k] - s0[k] for k in STATS})
        assert not ctx.tracer.wrapped
        out["records"] = list(ctx.tracer.ring)

    # thirty plain iterations across workers are more records than the
    # flight recorder's default ring holds
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("THRILL_TPU_TRACE_RING", "8192")
        RunLocalMock(job, workers)
    return out


@pytest.fixture(scope="module", params=[1, 4], ids=["w1", "w4"])
def run(request):
    return three_jobs(request.param) | {"workers": request.param}


def _spans(run):
    return {r["span"]: r for r in run["records"]
            if r.get("kind") != "instant"}


def _by_pipe(run):
    """The span records of each job: those under the roots of its
    pipeline, in the order of the jobs."""
    spans = _spans(run)

    def root(rec):
        while rec.get("parent") in spans:
            rec = spans[rec["parent"]]
        return rec

    jobs = {}
    for r in spans.values():
        top = root(r)
        assert top["cat"] == "stage" and "pipe" in top, r
        jobs.setdefault(top["pipe"], []).append(r)
    return [jobs[p] for p in sorted(jobs)]


def test_the_program_equals_the_numpy_reference(run):
    for got, want in zip(run["got"], run["want"]):
        assert got.shape == (10, 3) and got.dtype == np.float64
        assert np.abs(got - want).max() < 1e-12 * run["km"].COORD_RANGE
        assert run["km"].compare({"c": got}, {"c": want}) == {
            "centers_missing": (0, 0),
            "center_err_max": (pytest.approx(0, abs=1e-12), 1e-9)}


def test_the_loop_joins_its_invariant_dias_pipeline(run):
    spans = _spans(run)
    jobs = _by_pipe(run)
    # one pipeline per job, its Distribute's: every span of the run lies
    # under a root stage that carries it
    assert len(jobs) == 3
    for recs in jobs:
        roots = [r for r in recs if r.get("parent") not in spans]
        assert [r["name"] for r in roots] == ["Iterate"]
        assert {"Distribute", "Cache"} <= {
            r["name"] for r in recs if r["cat"] == "stage"}
        loops = [r for r in recs if r["cat"] == "loop"]
        assert loops and all(r["parent"] == roots[0]["span"]
                             for r in loops)


def test_a_later_job_takes_over_the_first_jobs_tape(run):
    first, second, third = run["stats"]
    names = [[r["name"] for r in recs if r["cat"] == "loop"]
             for recs in _by_pipe(run)]
    if run["workers"] > 1:
        # across workers the exchange's send matrix is computed from
        # the labels, so from the carry: no tape (tests/api/test_loop.py)
        assert all(s["loop_plan_builds"] == s["loop_plan_rebinds"] == 0
                   for s in run["stats"])
        assert all(set(n) == {"capture"} for n in names)
        return
    assert first["loop_plan_builds"] == 1
    assert first["loop_fori_iters"] == 9
    assert names[0] == ["capture", "replay"]
    for later, loops in zip((second, third), names[1:]):
        assert later["loop_plan_builds"] == 0
        assert later["loop_plan_rebinds"] == 1
        assert later["loop_fori_iters"] == 10
        assert later["loop_replay_fallbacks"] == 0
        # one whole-loop program, and nothing else
        assert later["device_dispatches"] == 1
        assert loops == ["rebind", "replay"]


def _plans(recs):
    return [r["index_plans"] for r in recs
            if r["cat"] == "dispatch"
            or (r["cat"] == "loop" and r["name"] == "replay"
                and "index_plans" in r)]


def test_the_spans_count_the_index_plans_the_counter_counts(run):
    for recs, stats in zip(_by_pipe(run), run["stats"]):
        assert all("index_plans" in r for r in recs
                   if r["cat"] == "dispatch")
        # the label is computed from the carry: a plan in every
        # iteration, none hoisted ahead of the loop; ten rows fold
        # densely, so every one of them is a dense fold's
        assert sum(_plans(recs)) == stats["r2i_index_plans"] == 10
        assert stats["r2i_dense_plans"] == 10
    if run["workers"] == 1:
        # a whole-loop dispatch's plans are on the replay span around
        # it, and the dispatch span under it carries none
        whole = [r for r in _by_pipe(run)[1] if r["cat"] == "loop"
                 and r["name"] == "replay"]
        assert [(r["fori_iters"], r["index_plans"]) for r in whole] \
            == [(10, 10)]
        assert [r["index_plans"] for r in _by_pipe(run)[1]
                if r["cat"] == "dispatch"] == [0]


def test_the_window_rule_and_the_phase_sums_hold(run):
    span_window = _load("chipbench", "span_window")
    loop_window = _load("chipbench", "loop_window")
    jobs = span_window.window_jobs(run["records"], 2)
    assert jobs is not None and len(jobs) == 2
    assert span_window.window_jobs(run["records"], 3) is None
    p = span_window.sum_phases(jobs)
    loops = loop_window.sum_loops(jobs)
    six = sum(p[k] for k in ("upload_s", "dispatch_call_s", "sync_wait_s",
                             "fetch_s", "host_plan_s", "compile_s"))
    assert six + loops["self_s"] == pytest.approx(p["root_stage_s"],
                                                  rel=0.01)
    if run["workers"] == 1:
        assert (loops["captures"], loops["rebinds"]) == (0, 2)
        assert loops["iterations_replayed"] == loops["iterations"] == 20


def test_pageranks_spans_count_its_two_plans():
    """A loop whose index is an invariant: the plan of the degrees and
    the loop's one plan, hoisted ahead of the ten iterations."""
    pr = _load("chipbench", "jobs", "pagerank")
    # twice as many pages as a dense fold takes rows: both folds are the
    # sorted ones, as at the cell's 2^17 pages
    traffic = {"graph500_scale": DENSE_FOLD_ROWS.bit_length(),
               "edge_factor": 16, "iterations": 10, "damping": 0.85}
    seen = []

    def job(ctx):
        for seed in (1, 2):
            inp = pr.generate(seed, traffic, {})
            s0 = ctx.overall_stats()
            n0 = len(ctx.tracer.ring)
            pr.pipeline(ctx, inp)
            s1 = ctx.overall_stats()
            seen.append((s1["r2i_index_plans"] - s0["r2i_index_plans"],
                         list(ctx.tracer.ring)[n0:],
                         s1["r2i_dense_plans"] - s0["r2i_dense_plans"]))
        assert not ctx.tracer.wrapped

    RunLocalMock(job, 1)
    # the job that captures sorts once more, in its captured iteration
    assert [delta for delta, _, _ in seen] == [3, 2]
    for delta, recs, dense in seen:
        assert sum(_plans([r for r in recs
                           if r.get("kind") != "instant"])) == delta
        assert dense == 0


def test_without_fusion_the_centroids_are_the_same_bit_for_bit(
        run, monkeypatch):
    if run["workers"] > 1:
        pytest.skip("one run per op is compared at W = 1")
    monkeypatch.setenv("THRILL_TPU_FUSE", "0")
    unfused = three_jobs(1, run["km"])
    for got, same in zip(run["got"], unfused["got"]):
        assert np.array_equal(got, same)
    for recs, stats in zip(_by_pipe(unfused), unfused["stats"]):
        assert sum(_plans(recs)) == stats["r2i_index_plans"] == 10
        assert stats["r2i_dense_plans"] == 10
