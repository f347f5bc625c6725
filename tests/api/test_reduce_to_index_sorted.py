"""ReduceToIndex with 8-byte sums, through the public op: the fold over
runs sorted by index (core/segmented.py ``sorted_fold_*``) gives what
numpy gives, at every mesh width, for every shape of index; narrower
leaves keep the single-operand scatter. The choice is made from the
leaf's dtype width, spec and padded range alone, so the CPU mesh runs
the chip's path; every range here is wider than a dense fold takes
(``DENSE_FOLD_ROWS``: tests/api/test_reduce_to_index_dense.py)."""

import re
import zlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from thrill_tpu.api import Context, FieldReduce
from thrill_tpu.api.ops import reduce as reduce_mod
from thrill_tpu.parallel.mesh import MeshExec

# dense rows; not a multiple of any mesh width, and more on each of four
# workers than a dense fold takes
SIZE = 4 * reduce_mod.DENSE_FOLD_ROWS + 13
N = 700
DTYPES = {"int64": np.int64, "uint64": np.uint64, "float64": np.float64}

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


def indices(shape, rng):
    if shape == "uniform":
        return rng.integers(0, SIZE, N)
    if shape == "one_target":
        return np.full(N, 17)
    if shape == "rmat":
        return np.minimum(rng.pareto(0.7, N).astype(np.int64), SIZE - 1)
    if shape == "empty_shard":
        # every index in the first worker's range: the other workers'
        # shards of the exchanged items are empty
        return rng.integers(0, 9, N)
    if shape == "masked":
        return rng.integers(0, SIZE, N)
    raise AssertionError(shape)


def values(dtype, rng):
    if dtype is np.float64:
        return rng.random(N) * 10.0 ** rng.integers(-9, 3, N)
    if dtype is np.uint64:
        return rng.integers(0, 2 ** 40, N).astype(np.uint64)
    return rng.integers(-2 ** 40, 2 ** 40, N)


def _bare_index(v):
    return jnp.floor(v / 4096).astype(jnp.int64)


def _field_index(t):
    return t["i"]


def _keep(t):
    return t["keep"] != 0


def _drop_keep(t):
    return {"i": t["i"], "tag": t["tag"], "v": t["v"]}


_SUM = FieldReduce("sum")
_FIRST_SUM = FieldReduce({"i": "first", "tag": "first", "v": "sum"})


def want_sum(idx, vals, keep, fill):
    out = np.full(SIZE, fill, vals.dtype)
    hit = np.zeros(SIZE, bool)
    hit[idx[keep]] = True
    if vals.dtype == np.float64:
        sums = np.bincount(idx[keep], weights=vals[keep], minlength=SIZE)
    else:
        sums = np.zeros(SIZE, vals.dtype)
        np.add.at(sums, idx[keep], vals[keep])
    out[hit] = sums[hit]
    return out, hit


def check_sum(got, want, dtype):
    got = np.asarray(got, dtype)
    if dtype is np.float64:
        np.testing.assert_allclose(got, want, rtol=1e-12, atol=0)
    else:
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("shape", ["uniform", "one_target", "rmat",
                                   "empty_shard", "masked"])
@pytest.mark.parametrize("tree", ["sum", "first_sum", "sum_neutral"])
@pytest.mark.parametrize("W", [1, 2, 4])
@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_eight_byte_sums_match_numpy(ctx_of, dtype, W, tree, shape):
    dtype = DTYPES[dtype]
    rng = np.random.default_rng(zlib.crc32(f"{tree}{shape}".encode()))
    ctx = ctx_of(W)
    idx, vals = indices(shape, rng), values(dtype, rng)
    keep = np.ones(N, bool)
    if shape == "masked":
        keep = rng.random(N) < 0.6
    p0 = ctx.overall_stats()["r2i_index_plans"]
    if tree == "sum":
        # a bare leaf that carries its own index: v = 4096 * index + r
        if dtype is np.float64:
            vals = idx * 4096.0 + rng.random(N) * 4095.0
        else:
            vals = (idx * 4096 + rng.integers(0, 4096, N)).astype(dtype)
        vals = np.where(keep, vals, vals[0]).astype(dtype)
        data = vals[keep] if shape == "masked" else vals
        out = ctx.Distribute(data).ReduceToIndex(_bare_index, _SUM, SIZE)
        got = out.AllGather()
        want, _ = want_sum(idx, vals, keep, 0)
        check_sum(got, want, dtype)
    else:
        data = {"i": idx, "tag": np.arange(N), "v": vals}
        dia = ctx.Distribute(dict(data, keep=keep.astype(np.int32)))
        dia = dia.Filter(_keep).Map(_drop_keep)
        neutral = ({"i": -1, "tag": -5, "v": 7} if tree == "sum_neutral"
                   else None)
        rows = dia.ReduceToIndex(_field_index, _FIRST_SUM, SIZE,
                                 neutral=neutral).AllGather()
        fill = 7 if tree == "sum_neutral" else 0
        want, hit = want_sum(idx, vals, keep, fill)
        check_sum([r["v"] for r in rows], want, dtype)
        # "first" is the first arrival, read off the same index plan
        first = np.full(SIZE, -5 if tree == "sum_neutral" else 0)
        for pos in np.flatnonzero(keep)[::-1]:
            first[idx[pos]] = pos
        np.testing.assert_array_equal([int(r["tag"]) for r in rows], first)
        np.testing.assert_array_equal(
            [int(r["i"]) for r in rows],
            np.where(hit, np.arange(SIZE),
                     -1 if tree == "sum_neutral" else 0))
    # one index plan per fold, on every mesh width
    assert ctx.overall_stats()["r2i_index_plans"] - p0 == 1


@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_out_of_range_rows_are_dropped(ctx_of, dtype):
    """As the scatter drops them: an index outside [0, size) cannot come
    through the exchange, and on one worker it goes to the dump row."""
    dtype = DTYPES[dtype]
    rng = np.random.default_rng(9)
    idx = rng.integers(-3, SIZE + 5, N)
    vals = values(dtype, rng)
    keep = (idx >= 0) & (idx < SIZE)
    rows = ctx_of(1).Distribute({"i": idx, "tag": np.arange(N), "v": vals}) \
        .ReduceToIndex(_field_index, _FIRST_SUM, SIZE).AllGather()
    want, _ = want_sum(idx, vals, keep, 0)
    check_sum([r["v"] for r in rows], want, dtype)


def _sorts(text):
    """Does the jaxpr sort anything: XLA's sort or the engine's loop?"""
    return re.search(r"\b(sort|while)\[", text) is not None


def _lowered(dtype, spec):
    cap, out_cap = 256, 2 * reduce_mod.DENSE_FOLD_ROWS
    tree = {"i": jnp.zeros(cap, jnp.int32), "v": jnp.zeros(cap, dtype)}
    pos = jnp.zeros(cap, jnp.int32)
    return str(jax.make_jaxpr(
        lambda t, p: reduce_mod._scatter_reduce_apply(
            t, p, out_cap, ["first", spec], None))(tree, pos))


@pytest.mark.parametrize("dtype", ["float32", "int32", "uint16"])
def test_narrow_sums_keep_the_scatter_and_sort_nothing(dtype):
    text = _lowered(jnp.dtype(dtype), "sum")
    assert "scatter-add" in text or "scatter_add" in text
    assert not _sorts(text), text


@pytest.mark.parametrize("dtype", ["float64", "int64", "uint64"])
def test_eight_byte_sums_scatter_no_value(dtype):
    text = _lowered(jnp.dtype(dtype), "sum")
    assert _sorts(text)                              # the index plan
    wide = {"float64": "f64", "int64": "i64", "uint64": "u64"}[dtype]
    for line in text.splitlines():
        if "scatter" in line:
            assert wide + "[" not in line, line


@pytest.mark.parametrize("dtype", ["float64", "int64"])
def test_eight_byte_min_and_max_keep_the_scatter(dtype):
    """Out of this path's scope: no chip record of them exists."""
    text = _lowered(jnp.dtype(dtype), "min")
    assert not _sorts(text), text
