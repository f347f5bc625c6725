"""A job that loops is one pipeline (api/loop.py, api/dia_base.py,
common/trace.py): the carry nodes join the pipeline of the loop's input,
``Iterate`` runs under a root ``stage`` span, its ``loop`` spans and the
dispatches, waits and fetches of an iteration hang under that, and a
later call of the same loop takes over the kept tape: no capture. The
window rule and the phase sums are the chip benchmark's own
(``chipbench/span_window.py``, ``chipbench/loop_window.py``)."""

import importlib.util
import os

import numpy as np
import pytest

from thrill_tpu.api import (Bind, FieldReduce, InnerJoin, Iterate,
                            RunLocalMock, Zip)

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
N, M, ITERATIONS, DAMPING = 256, 4096, 6, 0.85


def _load(name):
    spec = importlib.util.spec_from_file_location(
        name + "_t", os.path.join(_ROOT, "chipbench", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    for var in ("THRILL_TPU_LOOP_REPLAY", "THRILL_TPU_LOOP_FORI",
                "THRILL_TPU_FUSE", "THRILL_TPU_TRACE",
                "THRILL_TPU_TRACE_RING"):
        monkeypatch.delenv(var, raising=False)


# module-level functors and a module-level body: the same objects in
# every job, so that the tape of the first serves the others

def _src_one(e):
    return (e["s"], 1)


def _first(kv):
    return kv[0]


def _fill(kv, v):
    return kv[1] * 0.0 + v[0]


def _scale(r, kv):
    import jax.numpy as jnp
    return r / jnp.maximum(kv[1], 1)


def _edge_src(e):
    return e["s"]


def _joined(e, s):
    return {"d": e["d"], "v": s}


def _dst(c):
    return c["d"]


def _dampen(t, p):
    return p[0] + p[1] * t["v"]


_ADD_PAIRS = FieldReduce(("first", "sum"))
_SUM_V = FieldReduce({"d": "first", "v": "sum"})


def _iteration(ranks, links, degrees, n, params):
    contrib = InnerJoin(links, Zip(ranks, degrees, zip_fn=_scale),
                        _edge_src, None, _joined, dense_right_index=n)
    sums = contrib.ReduceToIndex(_dst, _SUM_V, n,
                                 neutral={"d": 0, "v": 0.0})
    return sums.Map(Bind(_dampen, params))


def pagerank(ctx, src, dst, iterations=ITERATIONS):
    """Every node derives from the one Distribute."""
    links = ctx.Distribute({"s": src, "d": dst}).Cache() \
        .Keep(iterations + 1)
    degrees = links.Map(_src_one).ReduceToIndex(
        _first, _ADD_PAIRS, N, neutral=(0, 0)).Cache().Keep(iterations + 1)
    ranks = degrees.Map(Bind(_fill, np.array([1.0 / N])))
    ranks = Iterate(ctx, _iteration, ranks, iterations, name="pagerank",
                    invariants=(links, degrees, N,
                                np.array([(1 - DAMPING) / N, DAMPING])))
    got = np.asarray(ranks.AllGather(), dtype=np.float64)
    links.Dispose()
    degrees.Dispose()
    return got


def dense(src, dst, iterations=ITERATIONS):
    deg = np.maximum(np.bincount(src, minlength=N), 1)
    r = np.full(N, 1.0 / N)
    for _ in range(iterations):
        r = (1 - DAMPING) / N + DAMPING * np.bincount(
            dst, weights=(r / deg)[src], minlength=N)
    return r


def graph(seed):
    rng = np.random.default_rng(seed)
    return rng.integers(0, N, M), rng.zipf(1.5, M) % N


def three_jobs():
    """A warm-up job and two more, each on a graph of its own; the
    finished run's span records and counter deltas per job."""
    out = {"stats": []}

    def job(ctx):
        for seed in (1, 2, 3):
            src, dst = graph(seed)
            s0 = ctx.overall_stats()
            got = pagerank(ctx, src, dst)
            s1 = ctx.overall_stats()
            np.testing.assert_allclose(got, dense(src, dst), rtol=1e-12)
            out["stats"].append({k: s1[k] - s0[k] for k in (
                "loop_plan_builds", "loop_plan_rebinds", "loop_fori_iters",
                "loop_replay_fallbacks", "device_dispatches")})
        assert not ctx.tracer.wrapped
        out["records"] = list(ctx.tracer.ring)

    RunLocalMock(job, 1)
    return out


@pytest.fixture(scope="module")
def run():
    return three_jobs()


def test_a_later_job_takes_over_the_first_jobs_tape(run):
    first, second, third = run["stats"]
    assert first["loop_plan_builds"] == 1
    assert first["loop_fori_iters"] == ITERATIONS - 1
    for later in (second, third):
        assert later["loop_plan_builds"] == 0
        assert later["loop_plan_rebinds"] == 1
        assert later["loop_fori_iters"] == ITERATIONS
        assert later["loop_replay_fallbacks"] == 0
        # the degrees, the first ranks, one whole-loop program
        assert later["device_dispatches"] == 3


def test_a_job_that_loops_is_one_pipeline(run):
    spans = {r["span"]: r for r in run["records"]
             if r.get("kind") != "instant"}
    stages = [r for r in spans.values() if r["cat"] == "stage"]
    assert all("pipe" in r for r in stages)
    # one pipeline per job: its Distribute's
    pipes = sorted({r["pipe"] for r in stages})
    assert len(pipes) == 3
    for pipe in pipes:
        mine = [r for r in stages if r["pipe"] == pipe]
        assert {r["name"] for r in mine} >= {
            "Distribute", "Iterate", "LoopCarry", "AllGather"}
        roots = [r["name"] for r in mine if r.get("parent") not in spans]
        assert roots == ["Iterate", "AllGather"]

    def root(rec):
        while rec.get("parent") in spans:
            rec = spans[rec["parent"]]
        return rec

    # the loop's spans hang directly under the root stage of the loop,
    # and every dispatch, wait and fetch under some stage
    loops = [r for r in spans.values() if r["cat"] == "loop"]
    assert loops and all(
        spans[r["parent"]]["name"] == "Iterate"
        and spans[r["parent"]]["cat"] == "stage" for r in loops)
    for r in spans.values():
        if r["cat"] in ("dispatch", "wait", "fetch", "upload", "fusion"):
            assert root(r)["cat"] == "stage" and "pipe" in root(r)
    by_pipe = {pipe: [r["name"] for r in loops
                      if root(r)["pipe"] == pipe] for pipe in pipes}
    assert by_pipe[pipes[0]] == ["capture", "replay"]
    assert by_pipe[pipes[1]] == by_pipe[pipes[2]] == ["rebind", "replay"]


def test_the_window_rule_and_the_phase_sums_hold_for_a_loop(run):
    span_window, loop_window = _load("span_window"), _load("loop_window")
    jobs = span_window.window_jobs(run["records"], 2)
    assert jobs is not None and len(jobs) == 2
    assert span_window.window_jobs(run["records"], 3) is None
    # without a device trace the window's last job is its first root
    # stage alone: Iterate, with the whole loop under it
    assert all(any(r["cat"] == "loop" for r in j) for j in jobs)
    p = span_window.sum_phases(jobs)
    loops = loop_window.sum_loops(jobs)
    six = sum(p[k] for k in ("upload_s", "dispatch_call_s", "sync_wait_s",
                             "fetch_s", "host_plan_s", "compile_s"))
    assert six + loops["self_s"] == pytest.approx(p["root_stage_s"],
                                                  rel=0.01)
    assert loops["captures"] == 0 and loops["rebinds"] == 2
    assert loops["iterations_replayed"] == loops["iterations"] \
        == 2 * ITERATIONS
    assert "stage:Iterate" in p["self_s_by_span"]


def test_without_a_dia_carry_iterate_still_has_a_root_stage():
    import jax.numpy as jnp
    seen = {}

    def step(c):
        return c

    def job(ctx):
        Iterate(ctx, step, {"x": jnp.zeros(4)}, 3, name="tree")
        seen["records"] = list(ctx.tracer.ring)

    RunLocalMock(job, 1)
    roots = [r for r in seen["records"] if r["cat"] == "stage"
             and r["name"] == "Iterate"]
    assert len(roots) == 1 and "pipe" not in roots[0]
    assert all(r.get("parent") == roots[0]["span"]
               for r in seen["records"] if r["cat"] == "loop")
