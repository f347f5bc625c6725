"""The fold over runs sorted by a dense index (core/segmented.py
``sorted_fold_plan`` / ``sorted_fold_sum`` / ``sorted_fold_first``):
ReduceToIndex's path for 8-byte sums, with no scatter of a value in it.
Integers equal ``np.add.at`` exactly, floats ``np.bincount(weights=)``
within 1e-12 relative; dropped rows (the dump row) are never read."""

import hashlib
import zlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from thrill_tpu.core import segmented

DTYPES = [np.int64, np.uint64, np.float64]
ROWS = 37


def targets(shape, n, rng):
    """Target rows in [0, ROWS], ROWS being the dump row."""
    if shape == "uniform":
        return rng.integers(0, ROWS, n)
    if shape == "one_target":
        return np.full(n, 11)
    if shape == "rmat":
        # a few hot rows take most items, most rows take none or one
        return np.minimum((rng.pareto(0.7, n)).astype(np.int64), ROWS - 1)
    if shape == "empty":
        return np.full(n, ROWS)                 # every item dropped
    if shape == "dropped":
        pos = rng.integers(0, ROWS, n)
        pos[rng.random(n) < 0.4] = ROWS
        return pos
    raise AssertionError(shape)


def values(dtype, n, rng, trail=()):
    if dtype is np.float64:
        return rng.random((n,) + trail) * 10.0 ** rng.integers(
            -12, 3, (n,) + trail)
    if dtype is np.uint64:
        return rng.integers(0, 2 ** 63, (n,) + trail).astype(np.uint64) * 2
    return rng.integers(-2 ** 62, 2 ** 62, (n,) + trail)


def reference_sum(pos, vals):
    keep = pos < ROWS
    out = np.zeros((ROWS,) + vals.shape[1:], vals.dtype)
    if vals.dtype == np.float64:
        flat = vals.reshape(len(vals), -1)
        cols = [np.bincount(pos[keep], weights=flat[keep, j],
                            minlength=ROWS) for j in range(flat.shape[1])]
        return np.stack(cols, axis=1).reshape(out.shape)
    with np.errstate(over="ignore"):
        np.add.at(out, pos[keep], vals[keep])
    return out


def fold(pos, vals):
    plan = segmented.sorted_fold_plan(jnp.asarray(pos, jnp.int32), ROWS)
    return plan, np.asarray(segmented.sorted_fold_sum(jnp.asarray(vals),
                                                      plan))


@pytest.mark.parametrize("shape", ["uniform", "one_target", "rmat",
                                   "empty", "dropped"])
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("n", [1, 200])
def test_sum_matches_numpy(dtype, shape, n):
    rng = np.random.default_rng(zlib.crc32(f"{shape}{n}".encode()))
    pos, vals = targets(shape, n, rng), values(dtype, n, rng)
    _, got = fold(pos, vals)
    want = reference_sum(pos, vals)
    assert got.dtype == vals.dtype and got.shape == want.shape
    if dtype is np.float64:
        np.testing.assert_allclose(got, want, rtol=1e-12, atol=0)
    else:
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("dtype", DTYPES)
def test_sum_of_rows_with_trailing_dims(dtype):
    rng = np.random.default_rng(3)
    pos, vals = targets("dropped", 300, rng), values(dtype, 300, rng, (3,))
    _, got = fold(pos, vals)
    want = reference_sum(pos, vals)
    if dtype is np.float64:
        np.testing.assert_allclose(got, want, rtol=1e-12, atol=0)
    else:
        np.testing.assert_array_equal(got, want)


def test_plan_is_a_stable_sort_with_run_boundaries():
    rng = np.random.default_rng(5)
    pos = targets("dropped", 500, rng)
    perm, offsets, starts = (np.asarray(a) for a in
                             segmented.sorted_fold_plan(
                                 jnp.asarray(pos, jnp.int32), ROWS))
    n = len(pos)
    # behind the items the place of no item, a run of its own
    assert perm[n] == n and starts[n]
    perm, starts = perm[:n], starts[:n]
    np.testing.assert_array_equal(perm, np.argsort(pos, kind="stable"))
    counts = np.bincount(pos, minlength=ROWS + 1)
    np.testing.assert_array_equal(
        offsets, np.concatenate([[0], np.cumsum(counts[:ROWS])]))
    sorted_pos = pos[perm]
    want = np.ones(len(pos), bool)
    want[1:] = sorted_pos[1:] != sorted_pos[:-1]
    np.testing.assert_array_equal(starts, want)


@pytest.mark.parametrize("dtype", DTYPES + [np.int32, np.uint8])
def test_first_is_the_first_arrival(dtype):
    rng = np.random.default_rng(7)
    n = 400
    pos = targets("dropped", n, rng)
    vals = rng.integers(0, 200, (n, 2)).astype(dtype)
    plan = segmented.sorted_fold_plan(jnp.asarray(pos, jnp.int32), ROWS)
    got, present = (np.asarray(a) for a in segmented.sorted_fold_first(
        jnp.asarray(vals), plan))
    for row in range(ROWS):
        hits = np.flatnonzero(pos == row)
        assert bool(present[row]) == (len(hits) > 0)
        if len(hits):
            np.testing.assert_array_equal(got[row], vals[hits[0]])


def test_a_small_run_beside_a_large_one_keeps_its_precision():
    """A prefix sum with differences at the run boundaries would carry
    the large run's absolute error (1e-13 near 1.0) into a run whose sum
    is 1e-10: 1e-3 relative. The fold adds terms of one run only."""
    rng = np.random.default_rng(11)
    big = rng.random(20000) / 10000.0           # sums to about 1.0
    small = rng.random(50) * 4e-12              # sums to about 1e-10
    pos = np.concatenate([np.zeros(20000, np.int64),
                          np.ones(50, np.int64),
                          np.full(20000, 2)])
    vals = np.concatenate([big, small, big])
    order = rng.permutation(len(pos))
    _, got = fold(pos[order], vals[order])
    want = reference_sum(pos[order], vals[order])
    np.testing.assert_allclose(got[:3], want[:3], rtol=1e-13, atol=0)
    assert abs(got[1] - want[1]) / want[1] < 1e-13


def test_the_fold_lowers_without_a_scatter_of_a_value():
    """The point of the fold: an 8-byte value is gathered and scanned,
    never scattered (XLA:TPU's two-operand scatter, 122-126 ns per
    update on a v5e); the plan scatters 32-bit counts and flags only."""
    pos = jnp.zeros(256, jnp.int32)
    for dtype in (jnp.float64, jnp.int64):
        vals = jnp.zeros(256, dtype)
        plan = jax.eval_shape(
            lambda p: segmented.sorted_fold_plan(p, ROWS), pos)
        text = str(jax.make_jaxpr(segmented.sorted_fold_sum)(vals, plan))
        assert "scatter" not in text, text
    text = str(jax.make_jaxpr(
        lambda p: segmented.sorted_fold_plan(p, ROWS))(pos))
    for line in text.splitlines():
        if "scatter" in line:
            assert "f64" not in line and "i64" not in line \
                and "u64" not in line, line


def test_segmented_reduce_fields_is_what_it_was():
    """WordCount's fold (``wordcount.w1``) shares ``FieldReduce`` with
    ReduceToIndex, not this code: its program, and so its compile-cache
    entry on the chip, is the parent's (PR 28) to the letter."""
    n = 64
    words = [jnp.zeros(n, jnp.uint64), jnp.zeros(n, jnp.uint64)]
    tree = {"w": jnp.zeros((n, 16), jnp.uint8), "c": jnp.zeros(n, jnp.int64)}
    valid = jnp.ones(n, bool)
    text = str(jax.make_jaxpr(
        lambda w, t, v: segmented.segmented_reduce_fields(
            w, t, v, ["sum", "first"]))(words, tree, valid))
    assert "while" not in text            # no sort engine loop
    assert hashlib.sha256(text.encode()).hexdigest() == _FIELDS_JAXPR_SHA256


# sha256 of the jaxpr above at the parent commit (PR 28), JAX 0.9.0
_FIELDS_JAXPR_SHA256 = (
    "8fbb9ccb3b1ca9dd0a3adec30a6bdf15b490173c8c709455cdb8c33d02b0b8c6")
