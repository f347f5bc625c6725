"""ReduceToIndex's dense fold (core/segmented.py ``dense_fold*``): a tree
with a "sum" of 8-byte values whose padded range has at most
``DENSE_FOLD_ROWS`` rows folds by masked reductions, one per row, with
no sort, histogram or gather of the items. Through the public op it
gives what numpy gives at every mesh width, for binary64 sums of ``[n]``
and ``[n, 3]`` leaves, int64 "first" and int32 "min" / "max" in one
tree; ``r2i_dense_plans`` counts the first-arrival plans it computes,
in place or once ahead of a loop whose index does not change."""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from thrill_tpu.api import Context, FieldReduce, Iterate, RunLocalMock
from thrill_tpu.api.ops import reduce as reduce_mod
from thrill_tpu.api.ops.reduce import DENSE_FOLD_ROWS
from thrill_tpu.parallel.mesh import MeshExec

SIZE = 37          # dense rows; not a multiple of any mesh width
N = 600

_CTX = {}


@pytest.fixture(scope="module")
def ctx_of():
    def get(W):
        if W not in _CTX:
            _CTX[W] = Context(MeshExec(num_workers=W))
        return _CTX[W]
    yield get
    for ctx in _CTX.values():
        ctx.close()
    _CTX.clear()


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    for var in ("THRILL_TPU_LOOP_REPLAY", "THRILL_TPU_LOOP_FORI",
                "THRILL_TPU_FUSE"):
        monkeypatch.delenv(var, raising=False)


def _row(t):
    return t["i"]


_ALL = FieldReduce({"i": "first", "tag": "first", "v": "sum", "x": "sum",
                    "lo": "min", "hi": "max"})
_SUMS = FieldReduce({"i": "first", "v": "sum"})
NEUTRAL = {"i": -1, "tag": -5, "v": 7.5, "x": np.array([2.5, -1.0, 0.25]),
           "lo": 99, "hi": -99}


def items(seed, size, lo=0, hi=None):
    """Items over every third row of ``[lo, hi)`` (the other rows no item
    reaches), with values over many magnitudes."""
    rng = np.random.default_rng(seed)
    hi = size if hi is None else hi
    rows = np.arange(lo, hi)
    idx = rng.choice(rows[rows % 3 != 1], N)
    return {"i": idx.astype(np.int64), "tag": np.arange(N, dtype=np.int64),
            "v": rng.random(N) * 10.0 ** rng.integers(-9, 3, N),
            "x": rng.random((N, 3)) * 1000.0 - 500.0,
            "lo": rng.integers(-2 ** 30, 2 ** 30, N).astype(np.int32),
            "hi": rng.integers(-2 ** 30, 2 ** 30, N).astype(np.int32)}


def want_rows(data, size, neutral):
    """numpy: per row the sums, min, max and first arrival of the items
    in range, the neutral (zeros without one) where none is."""
    nv = neutral or {k: 0 for k in ("i", "tag", "v", "lo", "hi")} | {
        "x": np.zeros(3)}
    out = {k: np.array([nv[k]] * size, data[k].dtype) for k in nv}
    for r in range(size):
        at = np.flatnonzero(data["i"] == r)
        if len(at):
            out["i"][r], out["tag"][r] = r, at[0]
            out["v"][r] = data["v"][at].sum()
            out["x"][r] = data["x"][at].sum(0)
            out["lo"][r] = data["lo"][at].min()
            out["hi"][r] = data["hi"][at].max()
    return out


def check(rows, want):
    got = {k: np.asarray([r[k] for r in rows]) for k in want}
    for k in ("i", "tag", "lo", "hi"):
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)
    for k in ("v", "x"):
        np.testing.assert_allclose(got[k], want[k], rtol=1e-12, atol=0,
                                   err_msg=k)


def plans(ctx):
    s = ctx.overall_stats()
    return s["r2i_index_plans"], s["r2i_dense_plans"]


def counted(ctx, before):
    return tuple(a - b for a, b in zip(plans(ctx), before))


@pytest.mark.parametrize("neutral", [None, NEUTRAL], ids=["zero", "neutral"])
@pytest.mark.parametrize("W", [1, 4])
def test_one_tree_of_every_field_matches_numpy(ctx_of, W, neutral):
    ctx = ctx_of(W)
    data = items(W, SIZE)
    before = plans(ctx)
    rows = ctx.Distribute(data).ReduceToIndex(
        _row, _ALL, SIZE, neutral=neutral).AllGather()
    check(rows, want_rows(data, SIZE, neutral))
    # its "first" fields read the first arrivals: one dense plan
    assert counted(ctx, before) == (1, 1)


@pytest.mark.parametrize("W", [1, 4])
def test_zero_neutral_sums_need_no_plan(ctx_of, W):
    ctx = ctx_of(W)
    data = items(10 + W, SIZE)
    before = plans(ctx)
    got = ctx.Distribute(data["v"]).ReduceToIndex(
        lambda v: jnp.zeros(v.shape, jnp.int64) + 3, FieldReduce("sum"),
        SIZE).AllGather()
    want = np.zeros(SIZE)
    want[3] = data["v"].sum()
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-12, atol=0)
    assert counted(ctx, before) == (0, 0)


def test_out_of_range_items_are_dropped(ctx_of):
    """On one worker an index outside [0, size) goes to the dump row,
    which no row of the dense fold matches."""
    ctx = ctx_of(1)
    data = items(7, SIZE, lo=-4, hi=SIZE + 6)
    rows = ctx.Distribute(data).ReduceToIndex(
        _row, _ALL, SIZE, neutral=NEUTRAL).AllGather()
    check(rows, want_rows(data, SIZE, NEUTRAL))


@pytest.mark.parametrize("factor, dense", [(1, 1), (2, 0)],
                         ids=["at_the_limit", "twice_it"])
@pytest.mark.parametrize("W", [1, 4])
def test_the_padded_range_chooses_the_engine(ctx_of, W, factor, dense):
    """A range whose padded rows per worker are exactly
    ``DENSE_FOLD_ROWS`` folds densely; twice that, over sorted runs."""
    ctx = ctx_of(W)
    size = W * DENSE_FOLD_ROWS * factor
    data = items(20 + W + factor, size)
    data = {k: data[k] for k in ("i", "v")}
    before = plans(ctx)
    rows = ctx.Distribute(data).ReduceToIndex(_row, _SUMS, size).AllGather()
    want = np.bincount(data["i"], weights=data["v"], minlength=size)
    np.testing.assert_allclose([r["v"] for r in rows], want, rtol=1e-12,
                               atol=0)
    assert counted(ctx, before) == (1, dense)


@pytest.mark.parametrize("W", [1, 4])
def test_without_fusion_the_rows_are_the_same_bit_for_bit(
        ctx_of, monkeypatch, W):
    data = items(30 + W, SIZE)

    def run():
        ctx = Context(MeshExec(num_workers=W))
        try:
            before = plans(ctx)
            rows = ctx.Distribute(data).ReduceToIndex(
                _row, _ALL, SIZE, neutral=NEUTRAL).AllGather()
            return rows, counted(ctx, before)
        finally:
            ctx.close()

    fused, n_fused = run()
    monkeypatch.setenv("THRILL_TPU_FUSE", "0")
    unfused, n_unfused = run()
    for a, b in zip(fused, unfused):
        for k in a:
            assert np.array_equal(np.asarray(a[k]), np.asarray(b[k])), k
    assert n_fused == n_unfused == (1, 1)


def test_the_dense_apply_sorts_and_scatters_nothing():
    cap, out_cap = 256, DENSE_FOLD_ROWS
    tree = {"i": jnp.zeros(cap, jnp.int64), "x": jnp.zeros((cap, 3)),
            "lo": jnp.zeros(cap, jnp.int32)}
    text = str(jax.make_jaxpr(
        lambda t, p: reduce_mod._scatter_reduce_apply(
            t, p, out_cap, ["first", "min", "sum"], None))(
        tree, jnp.zeros(cap, jnp.int32)))
    assert re.search(r"\b(sort|while)\[", text) is None, text
    assert "scatter" not in text, text


# -- in a loop ----------------------------------------------------------

ROWS, ITERATIONS = 24, 5


def _bucket(v):
    return {"i": jnp.floor(v * 7919.0).astype(jnp.int64) % ROWS, "v": v}


def _settle(t):
    return 0.25 + 0.5 * t["v"]


def _regroup(x):
    """The index is computed from the carry: a plan in every iteration."""
    return x.Map(_bucket).ReduceToIndex(_row, _SUMS, ROWS).Map(_settle)


def regroup_dense(x, n):
    for _ in range(n):
        idx = np.floor(x * 7919.0).astype(np.int64) % ROWS
        x = 0.25 + 0.5 * np.bincount(idx, weights=x, minlength=ROWS)
    return x


def _take_row(t, x):
    return {"i": t["i"], "v": x[t["src"]]}


def _summed(x, keys):
    """x[r] <- 1/4 + 1/2 sum of x[src] over the keys (src, r): the rows
    are the invariant ``keys``' column, the carry an array."""
    from thrill_tpu.api import Bind
    return keys.Map(Bind(_take_row, x)).ReduceToIndex(
        _row, _SUMS, ROWS).Map(_settle).AllGatherArrays()


def summed_dense(x, src, dst, n):
    for _ in range(n):
        x = 0.25 + 0.5 * np.bincount(dst, weights=x[src], minlength=ROWS)
    return x


def test_an_invariant_index_plans_once_per_call():
    rng = np.random.default_rng(40)
    src, dst = rng.integers(0, ROWS, 500), rng.integers(0, ROWS, 500)

    def job(ctx):
        keys = ctx.Distribute({"src": src, "i": dst}).Cache() \
            .Keep(3 * ITERATIONS)
        for k, seed in enumerate((41, 42, 43)):
            x = np.random.default_rng(seed).random(ROWS)
            before = plans(ctx)
            got = Iterate(ctx, _summed, x, ITERATIONS,
                          name="summed", invariants=(keys,))
            np.testing.assert_allclose(
                np.asarray(got), summed_dense(x, src, dst, ITERATIONS),
                rtol=1e-12)
            # the call that captures plans in its captured iteration and
            # once ahead of the whole-loop program; a call that rebinds
            # the tape, once
            assert counted(ctx, before) == ((2, 2) if k == 0 else (1, 1))

    RunLocalMock(job, 1)


def test_a_carry_dependent_index_plans_in_every_iteration():
    def job(ctx):
        for seed in (44, 45):
            x = np.random.default_rng(seed).random(ROWS)
            before = plans(ctx)
            got = np.asarray(Iterate(ctx, _regroup, ctx.Distribute(x),
                                     ITERATIONS, name="regroup")
                             .AllGather(), np.float64)
            np.testing.assert_allclose(got, regroup_dense(x, ITERATIONS),
                                       rtol=1e-12)
            assert counted(ctx, before) == (ITERATIONS, ITERATIONS)

    RunLocalMock(job, 1)
