"""ReduceToIndex's index plan in a loop (api/fusion.py Segment.index_plan,
api/loop.py ``run_fori``): where the index is an invariant of the loop,
the whole-loop program sorts it once, ahead of the iterations, on the
call that captured the tape and on every call that rebinds it; where the
index changes with the carry, the plan runs in every iteration and the
tape replays all the same. ``r2i_index_plans`` counts the plans run. The
range is wider than a dense fold takes, so these are the plans of the
fold over sorted runs (the dense fold's own loops:
tests/api/test_reduce_to_index_dense.py)."""

import numpy as np
import pytest

import jax.numpy as jnp

from thrill_tpu.api import (Bind, FieldReduce, InnerJoin, Iterate,
                            RunLocalMock)
from thrill_tpu.api.ops.reduce import DENSE_FOLD_ROWS

N, M, ITERATIONS = 2 * DENSE_FOLD_ROWS, 2048, 5


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    for var in ("THRILL_TPU_LOOP_REPLAY", "THRILL_TPU_LOOP_FORI",
                "THRILL_TPU_FUSE"):
        monkeypatch.delenv(var, raising=False)


# module-level functors and bodies: the same objects in every call, so
# that a later call takes over the tape of the first

def _edge_src(e):
    return e["s"]


def _joined(e, x):
    return {"d": e["d"], "v": x * e["w"]}


def _dst(c):
    return c["d"]


def _mix(t, p):
    return p[0] + p[1] * t["v"]


_SUM_V = FieldReduce({"d": "first", "v": "sum"})


def _spread(x, edges, params):
    """x[d] <- p0 + p1 * sum over edges (s, d, w) of w * x[s]: the index
    is the edge list's ``d`` column, an invariant."""
    along = InnerJoin(edges, x, _edge_src, None, _joined,
                      dense_right_index=N)
    return along.ReduceToIndex(_dst, _SUM_V, N).Map(Bind(_mix, params))


def spread_dense(x, s, d, w, p, n):
    for _ in range(n):
        x = p[0] + p[1] * np.bincount(d, weights=w * x[s], minlength=N)
    return x


def graph(seed):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, N, M), rng.zipf(1.4, M) % N,
            rng.random(M) / 8.0, rng.random(N))


def counters(ctx):
    s = ctx.overall_stats()
    return {k: s[k] for k in ("r2i_index_plans", "loop_plan_builds",
                              "loop_plan_rebinds", "loop_fori_iters",
                              "loop_replay_fallbacks", "device_dispatches")}


def delta(ctx, before):
    return {k: v - before[k] for k, v in counters(ctx).items()}


def spread(ctx, seed, dtype=np.float64):
    s, d, w, x = graph(seed)
    p = np.array([0.125, 0.5])
    edges = ctx.Distribute({"s": s, "d": d, "w": w.astype(dtype)}) \
        .Cache().Keep(ITERATIONS)
    before = counters(ctx)
    got = Iterate(ctx, _spread, ctx.Distribute(x.astype(dtype)),
                  ITERATIONS, name="spread",
                  invariants=(edges, p.astype(dtype))).AllGather()
    return (np.asarray(got, np.float64), delta(ctx, before),
            spread_dense(x, s, d, w, p, ITERATIONS))


def test_an_invariant_index_is_sorted_once_per_call():
    def job(ctx):
        got, first, want = spread(ctx, 1)
        np.testing.assert_allclose(got, want, rtol=1e-12)
        # the captured iteration sorted in place; the whole-loop program
        # of the other four sorts once, ahead of them
        assert first["loop_plan_builds"] == 1
        assert first["loop_fori_iters"] == ITERATIONS - 1
        assert first["r2i_index_plans"] == 2
        for seed in (2, 3):
            got, later, want = spread(ctx, seed)
            # its own edges' answer, off one plan for five iterations,
            # in the one dispatch of the whole loop
            np.testing.assert_allclose(got, want, rtol=1e-12)
            assert later["loop_plan_rebinds"] == 1
            assert later["loop_plan_builds"] == 0
            assert later["loop_fori_iters"] == ITERATIONS
            assert later["loop_replay_fallbacks"] == 0
            assert later["r2i_index_plans"] == 1
            assert later["device_dispatches"] == 1

    RunLocalMock(job, 1)


def test_a_tape_replayed_call_by_call_sorts_in_every_iteration(monkeypatch):
    """Without the whole-loop program (THRILL_TPU_LOOP_FORI=0) every
    replayed dispatch computes its plan in place: the same answer."""
    monkeypatch.setenv("THRILL_TPU_LOOP_FORI", "0")

    def job(ctx):
        for seed in (4, 5):
            got, d, want = spread(ctx, seed)
            np.testing.assert_allclose(got, want, rtol=1e-12)
            assert d["r2i_index_plans"] == ITERATIONS
            assert d["loop_fori_iters"] == 0

    RunLocalMock(job, 1)


def test_binary32_sums_run_no_plan():
    def job(ctx):
        for seed in (6, 7):
            got, d, want = spread(ctx, seed, np.float32)
            np.testing.assert_allclose(got, want, rtol=2e-5)
            assert d["r2i_index_plans"] == 0

    RunLocalMock(job, 1)


# -- an index that changes with the carry (k-means-like) ----------------

def _to_item(v):
    return {"i": jnp.floor(v * 7919.0).astype(jnp.int64) % N, "v": v}


def _item_index(t):
    return t["i"]


def _settle(t):
    return 0.25 + 0.5 * t["v"]


_SUM_ITEM = FieldReduce({"i": "first", "v": "sum"})


def _regroup(x):
    """Every value goes to the row its own size names, and the rows'
    sums are the next values: the index is computed from the carry."""
    return x.Map(_to_item).ReduceToIndex(_item_index, _SUM_ITEM, N) \
        .Map(_settle)


def regroup_dense(x, n):
    for _ in range(n):
        idx = np.floor(x * 7919.0).astype(np.int64) % N
        x = 0.25 + 0.5 * np.bincount(idx, weights=x, minlength=N)
    return x


def test_a_carry_dependent_index_is_sorted_in_every_iteration():
    def job(ctx):
        for k, seed in enumerate((8, 9)):
            x = np.random.default_rng(seed).random(N)
            before = counters(ctx)
            got = np.asarray(Iterate(ctx, _regroup, ctx.Distribute(x),
                                     ITERATIONS, name="regroup")
                             .AllGather(), np.float64)
            d = delta(ctx, before)
            np.testing.assert_allclose(got, regroup_dense(x, ITERATIONS),
                                       rtol=1e-12)
            # the tape still replays: one captured iteration and the
            # rest in the whole-loop program, then all of them in it on
            # the call that takes the tape over; a plan in each
            assert d["loop_fori_iters"] == ITERATIONS - (k == 0)
            assert d["loop_replay_fallbacks"] == 0
            assert d["r2i_index_plans"] == ITERATIONS

    RunLocalMock(job, 1)
