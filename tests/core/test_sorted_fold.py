"""The folds over sorted runs in core/segmented.py, with no scatter of
a value in them.

``sorted_fold_plan`` / ``sorted_fold_sum`` / ``sorted_fold_first``:
ReduceToIndex's path for 8-byte sums over runs sorted by a dense index.
Integers equal ``np.add.at`` exactly, floats ``np.bincount(weights=)``
within 1e-12 relative; dropped rows (the dump row) are never read.

``reduce_runs`` (ReduceByKey's fold of key-sorted runs, second half of
the file): one row per run, compact and in key order, gathered at the
run boundaries; bit for bit what the scatters it replaced gave (PR 30's
program, kept below as the reference)."""

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


# ----------------------------------------------------------------------
# reduce_runs: one row per run by gathers at the run boundaries
# ----------------------------------------------------------------------

def _parent_rep_mask(starts, valid):
    n = valid.shape[0]
    next_start = jnp.roll(starts, -1).at[-1].set(True)
    count = jnp.sum(valid.astype(jnp.int32))
    return valid & (next_start | (jnp.arange(n) == count - 1))


def _parent_fields(words, tree, valid, flat_specs):
    """``segmented_reduce_fields`` as PR 30 had it: every run's result
    scattered by ``segment_sum`` / ``_min`` / ``_max``, spread back over
    the run's rows, one representative row marked."""
    import jax.ops as jops
    n = valid.shape[0]
    starts = segmented.segment_boundaries(words, valid)
    seg = jnp.clip(jnp.cumsum(starts.astype(jnp.int32)) - 1, 0, n - 1)
    leaves, td = jax.tree.flatten(tree)
    out = []
    for s, leaf in zip(flat_specs, leaves):
        v = segmented._bshape(valid, leaf)
        if s == "first":
            fdt = leaf.dtype
            if fdt == jnp.bool_:
                src = leaf.astype(jnp.int32)
            elif jnp.issubdtype(fdt, jnp.floating):
                src = jax.lax.bitcast_convert_type(
                    leaf, jnp.dtype(f"uint{fdt.itemsize * 8}"))
            else:
                src = leaf
            contrib = jnp.where(segmented._bshape(starts, leaf), src,
                                jnp.zeros_like(src))
            res = jops.segment_sum(contrib, seg, num_segments=n,
                                   indices_are_sorted=True)
            if fdt == jnp.bool_:
                res = res.astype(jnp.bool_)
            elif jnp.issubdtype(fdt, jnp.floating):
                res = jax.lax.bitcast_convert_type(res, fdt)
        elif s == "sum":
            res = jops.segment_sum(jnp.where(v, leaf, jnp.zeros_like(leaf)),
                                   seg, num_segments=n,
                                   indices_are_sorted=True)
        elif s == "min":
            fill = jnp.array(jnp.iinfo(leaf.dtype).max, leaf.dtype)
            res = jops.segment_min(jnp.where(v, leaf, fill), seg,
                                   num_segments=n, indices_are_sorted=True)
        else:
            fill = jnp.array(jnp.iinfo(leaf.dtype).min, leaf.dtype)
            res = jops.segment_max(jnp.where(v, leaf, fill), seg,
                                   num_segments=n, indices_are_sorted=True)
        out.append(jnp.take(res, seg, axis=0))
    return words, jax.tree.unflatten(td, out), _parent_rep_mask(starts, valid)


def _parent_generic(words, tree, valid, reduce_fn):
    """``segmented_reduce`` as PR 30 had it: the scan, and a mask."""
    starts = segmented.segment_boundaries(words, valid)

    def combine(a, b):
        tree_a, flag_a = a
        tree_b, flag_b = b
        merged = reduce_fn(tree_a, tree_b)
        keep_b = jax.tree.map(
            lambda m, vb: jnp.where(segmented._bshape(flag_b, m), vb, m),
            merged, tree_b)
        return keep_b, flag_a | flag_b

    scanned, _ = jax.lax.associative_scan(combine, (tree, starts), axis=0)
    return words, scanned, _parent_rep_mask(starts, valid)


def parent_reduce_runs(words, tree, valid, reduce_fn, specs):
    """What ReduceByKey's local phase ran before PR 31: the fold, then
    ``compact_valid`` on the representatives."""
    from thrill_tpu.data.shards import compact_valid
    if specs is not None:
        words, tree, rep = _parent_fields(words, tree, valid, specs)
    else:
        words, tree, rep = _parent_generic(words, tree, valid, reduce_fn)
    (words, tree), count = compact_valid((words, tree), rep)
    return words, tree, count


N = 96          # the scan shifts by 1, 2, 4, ... 64


def run_lengths(shape, rng):
    """(lengths of the key runs, rows that are valid) of N sorted rows."""
    if shape == "random":
        cuts = np.sort(rng.choice(np.arange(1, 80), 17, replace=False))
        return np.diff(np.concatenate([[0], cuts, [80]])), 80
    if shape == "no_valid_row":
        return np.array([N]), 0
    if shape == "one_run":
        return np.array([N - 9]), N - 9
    if shape == "every_row_its_own_run":
        return np.ones(N, np.int64), N
    if shape == "invalid_tail_behind_the_last_run":
        # the tail repeats the last run's key: only ``valid`` ends it
        return np.array([5, 1, 30, 7]), 43 - 3
    if shape == "runs_across_every_shift":
        # runs begin one row before 1, 2, 4, ... 64 and end behind them
        return np.array([1, 2, 4, 8, 16, 32, 33]), N
    if shape == "a_run_of_length_n":
        return np.array([N]), N
    raise AssertionError(shape)


def sorted_rows(shape, seed):
    rng = np.random.default_rng(seed)
    lens, count = run_lengths(shape, rng)
    keys = np.repeat(np.arange(len(lens)) * 3 + 1, lens)
    keys = np.concatenate([keys, np.full(N - len(keys), keys[-1])])[:N]
    words = [jnp.asarray(keys, jnp.uint64),
             jnp.asarray(keys % 2, jnp.uint64) * 0 + 7]
    tree = {"w": jnp.asarray(rng.integers(0, 256, (N, 16)), jnp.uint8),
            "c": jnp.asarray(rng.integers(-2 ** 62, 2 ** 62, N)),
            "lo": jnp.asarray(rng.integers(-99, 99, (N, 2)), jnp.int32),
            "hi": jnp.asarray(rng.integers(0, 2 ** 63, N), jnp.uint64),
            "f": jnp.asarray(np.where(rng.random(N) < 0.3, -0.0,
                                      rng.standard_normal(N))),
            "b": jnp.asarray(rng.random(N) < 0.5)}
    return words, tree, jnp.arange(N) < count


FIELD_SPECS = {"w": "first", "c": "sum", "lo": "min", "hi": "max",
               "f": "first", "b": "first"}


def _generic_fn(a, b):
    """Associative, not commutative in ``w``: the scan's order shows."""
    return {"w": a["w"], "c": a["c"] + b["c"],
            "lo": jnp.minimum(a["lo"], b["lo"]),
            "hi": jnp.maximum(a["hi"], b["hi"]),
            "f": b["f"], "b": a["b"] ^ b["b"]}


SHAPES = ["random", "no_valid_row", "one_run", "every_row_its_own_run",
          "invalid_tail_behind_the_last_run", "runs_across_every_shift",
          "a_run_of_length_n"]


@pytest.mark.parametrize("engine", ["fields", "generic"])
@pytest.mark.parametrize("shape", SHAPES)
def test_reduce_runs_is_bit_for_bit_the_compacted_parent(shape, engine):
    words, tree, valid = sorted_rows(shape, zlib.crc32(shape.encode()))
    td = jax.tree.structure(tree)
    specs = (jax.tree.leaves(FIELD_SPECS) if engine == "fields" else None)
    assert specs is None or td == jax.tree.structure(FIELD_SPECS)
    fn = None if engine == "fields" else _generic_fn
    got_w, got_t, n_runs = jax.jit(
        lambda w, t, v: segmented.reduce_runs(w, t, v, fn, specs))(
            words, tree, valid)
    want_w, want_t, count = jax.jit(
        lambda w, t, v: parent_reduce_runs(w, t, v, fn, specs))(
            words, tree, valid)
    lens, nvalid = run_lengths(shape, np.random.default_rng(
        zlib.crc32(shape.encode())))
    assert int(n_runs) == int(count) == (len(lens) if nvalid else 0)
    for g, w in zip(got_w, want_w):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    for name in tree:
        g, w = np.asarray(got_t[name]), np.asarray(want_t[name])
        assert g.dtype == w.dtype and g.shape == w.shape
        # bit for bit: -0.0 and 0.0 differ, and rows past n_runs are
        # the zeros a compaction leaves
        np.testing.assert_array_equal(g.view(np.uint8), w.view(np.uint8),
                                      err_msg=name)
        assert not g[int(n_runs):].any()


def test_float_sums_add_each_runs_terms_alone():
    """Another order of additions than ``segment_sum``'s, within one run
    only: a tiny run beside a large one keeps its precision, and a run
    that sums to -0.0 keeps its sign (the generic engine's reading)."""
    rng = np.random.default_rng(17)
    lens = np.array([40, 3, 2, 51])
    keys = np.repeat(np.arange(4), lens)
    vals = np.concatenate([rng.random(40) * 1e6, rng.random(3) * 1e-9,
                           [-0.0, -0.0], rng.standard_normal(51)])
    _, got, n_runs = segmented.reduce_runs(
        [jnp.asarray(keys, jnp.uint64)], {"v": jnp.asarray(vals)},
        jnp.ones(N, bool), None, ["sum"])
    got = np.asarray(got["v"])
    assert int(n_runs) == 4
    want = [vals[keys == k].sum() for k in range(4)]
    np.testing.assert_allclose(got[:4], want, rtol=1e-13, atol=0)
    assert got[2] == 0.0 and np.signbit(got[2])
    assert not got[4:].any()


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


CELL_TREE = {"c": np.int64, "w": (np.uint8, 16)}        # wordcount.w1's


@pytest.mark.parametrize("case, leaves, specs", [
    ("cell", CELL_TREE, ["sum", "first"]),
    ("min", {"v": np.int32, "k": np.uint64}, ["first", "min"]),
    ("max", {"v": np.int64, "k": (np.int16, 3)}, ["first", "max"]),
    ("float_sum", {"v": np.float64, "x": (np.float32, 2)}, ["sum", "sum"]),
    ("first_only", {"a": np.bool_, "z": np.complex64}, ["first", "first"]),
    ("generic", CELL_TREE, None),
])
def test_reduce_runs_scatters_no_value_and_loops_nowhere(case, leaves,
                                                         specs):
    """The point of the fold: a leaf is scanned and gathered, never
    scattered (XLA:TPU: 67-89 ns a row for ``u8[n, 16]`` and int64 rows,
    sorted indices or not). The run positions come out of a sort of one
    s32 operand; the one scatter left sets the flag of row 0."""
    n = 256
    words = [jnp.zeros(n, jnp.uint64), jnp.zeros(n, jnp.uint64)]
    tree = {k: jnp.zeros((n,) + ((v[1],) if isinstance(v, tuple) else ()),
                         v[0] if isinstance(v, tuple) else v)
            for k, v in leaves.items()}
    fn = None if specs is not None else (
        lambda a, b: {"c": a["c"] + b["c"], "w": a["w"]})
    eqns = list(_eqns(jax.make_jaxpr(
        lambda w, t, v: segmented.reduce_runs(w, t, v, fn, specs))(
            words, tree, jnp.ones(n, bool)).jaxpr))
    scattered = [e.invars[0].aval for e in eqns
                 if "scatter" in e.primitive.name]
    assert [(a.shape, a.dtype) for a in scattered] == [((n,), jnp.bool_)]
    sorted_ = [[v.aval for v in e.invars] for e in eqns
               if e.primitive.name == "sort"]
    assert [[(a.shape, a.dtype) for a in ops] for ops in sorted_] == [
        [((n,), jnp.int32)]]
    prims = {e.primitive.name for e in eqns}
    assert "while" not in prims and "gather" in prims
