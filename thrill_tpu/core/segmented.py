"""Sort-based segmented aggregation: the device reduce engine.

The reference aggregates with linear-probing hash tables
(reference: thrill/core/reduce_pre_phase.hpp:94,
reduce_by_hash_post_phase.hpp:44, reduce_probing_hash_table.hpp:77).
Hash tables are a pointer-chasing CPU idiom; the TPU-native equivalent
is sort + segmented reduction: XLA's bitonic sort groups equal keys into
runs, a segmented scan combines each run with the user's reduce
function, and one row per run is gathered from the run boundaries:
the output is born compact and in key order, and no value is scattered
on the way. Everything is static-shaped, branch-free and VPU/MXU
friendly.
"""

from __future__ import annotations

from typing import Any, Callable, List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import rowmove


def sort_by_key_words(words: List[jnp.ndarray], tree: Any, valid: jnp.ndarray,
                      extra_words: List[jnp.ndarray] = ()):
    """Stable sort of (words, tree, valid) with invalid items last.

    Returns (sorted_words, sorted_tree, sorted_valid, sorted_extra_words).
    ``extra_words`` sort after the key words (e.g. global index for
    stability). The words and the validity come back from the sort
    itself (``device_sort.sort_words``); only the tree's leaves are
    gathered by its permutation.
    """
    from .device_sort import sort_words
    invalid_first_word = (~valid).astype(jnp.uint32)  # valid(0) < invalid(1)
    nw = len(words)
    keys, perm = sort_words([invalid_first_word] + list(words)
                            + list(extra_words))
    # row movement by a permutation, like core/rowmove.py's: the same
    # name in a device profile
    with jax.named_scope(rowmove.SCOPE):
        tree_s = jax.tree.map(lambda x: jnp.take(x, perm, axis=0), tree)
    return keys[1:1 + nw], tree_s, keys[0] == 0, keys[1 + nw:]


def segment_boundaries(words: List[jnp.ndarray], valid: jnp.ndarray
                       ) -> jnp.ndarray:
    """starts[i] = True iff item i begins a new key run (valid items,
    assumed key-sorted with invalid last)."""
    n = valid.shape[0]
    idx = jnp.arange(n)
    diff = jnp.zeros(n, dtype=bool).at[0].set(True)
    neq = jnp.zeros(n, dtype=bool)
    for w in words:
        neq = neq | (w != jnp.roll(w, 1))
    diff = diff | neq
    return diff & valid


def run_bounds(starts: jnp.ndarray, valid: jnp.ndarray
               ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Where the runs lie, by output row: ``(start_of, end_of, n_runs)``.

    ``starts`` from :func:`segment_boundaries` over rows that are
    key-sorted with the invalid ones last. Output row ``k < n_runs`` is
    run ``k`` in key order: its first row is the sorted position
    ``start_of[k]``, its last ``end_of[k]`` (the position before the
    next start; the last run ends at the last VALID row, so the invalid
    tail behind it is in no run). Rows at and past ``n_runs`` point at
    ``n``, out of range, and gather as zeros. All [n] int32 and 32-bit
    work, and nothing is scattered: the positions of the set flags come
    out of one single-operand sort of ``where(starts, i, n)`` (7.4 ms
    for 2^22 rows on a v5e, where the 32-bit scatter of positions takes
    25.1; PERF.md section 6, PR 31)."""
    n = valid.shape[0]
    with jax.named_scope("run_bounds"):
        idx = jnp.arange(n, dtype=jnp.int32)
        n_runs = jnp.sum(starts.astype(jnp.int32))
        start_of = jax.lax.sort(jnp.where(starts, idx, n))
        next_start = jnp.concatenate([start_of[1:],
                                      jnp.full(1, n, jnp.int32)])
        count = jnp.sum(valid.astype(jnp.int32))
        end_of = jnp.where(idx < n_runs,
                           jnp.minimum(next_start, count) - 1, n)
    return start_of, end_of, n_runs


def _rows_at(leaf: jnp.ndarray, at: jnp.ndarray) -> jnp.ndarray:
    """``leaf[at]`` by rows; a position out of range reads zeros.

    XLA:TPU holds a 64-bit integer as two 32-bit halves and gathers
    each by itself; as ``u32[n, ..., 2]`` rows one gather moves both
    (19.3 ms against 61.1 for 2^22 int64, bit for bit; PERF.md section
    6, PR 31). Not for binary64: its halves are two binary32, which a
    bit-cast has to convert."""
    if leaf.dtype.itemsize == 8 and jnp.issubdtype(leaf.dtype, jnp.integer):
        halves = jax.lax.bitcast_convert_type(leaf, jnp.uint32)
        return jax.lax.bitcast_convert_type(_rows_at(halves, at), leaf.dtype)
    return jnp.take(leaf, at, axis=0, mode="fill", fill_value=0)


def segmented_reduce(words: List[jnp.ndarray], tree: Any,
                     valid: jnp.ndarray, reduce_fn: Callable
                     ) -> Tuple[List[jnp.ndarray], Any, jnp.ndarray]:
    """Combine each equal-key run into one item.

    Inputs must be key-sorted with invalid items last. Returns
    ``(words, tree, n_runs)``: row ``k < n_runs`` of the leaves is the
    fold of run ``k`` and of the words its key, compact and in key
    order; rows at and past ``n_runs`` are zero. The fold is a
    segmented inclusive scan read at every run's last row, so
    ``reduce_fn`` must be associative (same contract as the reference's
    reduce function).
    """
    starts = segment_boundaries(words, valid)
    start_of, end_of, n_runs = run_bounds(starts, valid)

    def combine(a, b):
        tree_a, flag_a = a
        tree_b, flag_b = b
        merged = reduce_fn(tree_a, tree_b)
        keep_b = jax.tree.map(
            lambda m, vb: jnp.where(_bshape(flag_b, m), vb, m),
            merged, tree_b)
        return keep_b, flag_a | flag_b

    with jax.named_scope("run_fold"):
        scanned, _ = jax.lax.associative_scan(combine, (tree, starts),
                                              axis=0)
        return ([_rows_at(w, start_of) for w in words],
                jax.tree.map(lambda l: _rows_at(l, end_of), scanned),
                n_runs)


def _bshape(flag, leaf):
    """Broadcast [n] flag against leaf [n, ...]."""
    return flag.reshape(flag.shape + (1,) * (leaf.ndim - 1))


@jax.named_scope("segmented_reduce")
def reduce_runs(words, tree, valid, reduce_fn, specs):
    """One dispatch point for every device reduce program: the
    per-field engine when ``specs`` (from FieldReduce, pre-gated by
    :func:`fields_specializable`) is available, else the generic
    associative scan. Same ``(words, tree, n_runs)`` contract either
    way: one row per run, compact and in key order, made by gathers at
    the run boundaries (:func:`run_bounds`), so nothing behind it has
    rows left to compact."""
    if specs is not None:
        return segmented_reduce_fields(words, tree, valid, specs)
    return segmented_reduce(words, tree, valid, reduce_fn)


def fields_specializable(flat_specs, leaf_dtypes) -> bool:
    """Can :func:`segmented_reduce_fields` handle this FieldReduce
    spec? "first" is a gather and takes any dtype; "sum" needs numeric
    (bool addition differs between numpy and the scan's `+`);
    "min"/"max" need INTEGER dtypes: the order in which NaNs and signed
    zeros meet is the generic scan's to decide, so floats keep it."""
    for s, dt in zip(flat_specs, leaf_dtypes):
        if s == "first":
            continue
        if s == "sum":
            if not (np.issubdtype(dt, np.integer)
                    or np.issubdtype(dt, np.floating)):
                return False
        elif s in ("min", "max"):
            if not np.issubdtype(dt, np.integer):
                return False
        else:
            return False
    return True


_FIELD_OPS = {"sum": jnp.add, "min": jnp.minimum, "max": jnp.maximum}


def segmented_reduce_fields(words: List[jnp.ndarray], tree: Any,
                            valid: jnp.ndarray, flat_specs
                            ) -> Tuple[List[jnp.ndarray], Any,
                                       jnp.ndarray]:
    """FieldReduce specialization of :func:`segmented_reduce`: same
    inputs and ``(words, tree, n_runs)`` contract, each field folded by
    itself. "first" is one gather at the run starts. "sum" / "min" /
    "max" are a segmented inclusive scan of the sorted leaf in log2(n)
    shifted passes (:func:`_segmented_scan`) and one gather at the run
    ends; the reference reaches the same shape by accumulating
    std::plus directly in its probing table. No value is scattered:
    XLA:TPU scatters 8-byte and ``u8[n, 16]`` rows at 67-89 ns a row,
    sorted indices or not (PERF.md section 6, PR 31).

    A scan adds the terms of one run only, the invalid tail behind the
    last run never reaches a row that is read, and a float sum adds
    each run's terms in sorted order, pairwise: the unordered-reduce
    contract. A run that sums to -0.0 keeps its sign, as in the generic
    engine. Caller gates with :func:`fields_specializable`.
    """
    starts = segment_boundaries(words, valid)
    start_of, end_of, n_runs = run_bounds(starts, valid)
    leaves, td = jax.tree.flatten(tree)
    with jax.named_scope("run_fold"):
        out_leaves = [
            _rows_at(leaf, start_of) if s == "first" else
            _rows_at(_segmented_scan(leaf, starts, _FIELD_OPS[s]), end_of)
            for s, leaf in zip(flat_specs, leaves)]
        return ([_rows_at(w, start_of) for w in words],
                jax.tree.unflatten(td, out_leaves), n_runs)


# ----------------------------------------------------------------------
# fold over runs sorted by a dense index (ReduceToIndex's 8-byte sums
# into more rows than a dense fold takes)
# ----------------------------------------------------------------------
# XLA:TPU carries a 64-bit value as a pair of 32-bit ones, so a scatter
# of 8-byte values is a two-operand scatter: 122-126 ns per update on a
# v5e against 6.8 ns for a 32-bit one on the same indices, sorted or not
# (PERF.md section 5). The fold below has no scatter of a value in it:
# an index plan made of 32-bit operations alone (a stable argsort of the
# target rows and the run boundaries), then per 8-byte column a gather
# by the permutation, a segmented inclusive scan and one gather at the
# run ends. The plan reads no value, so a loop whose index does not
# change computes it once (api/fusion.py Segment.index_plan, api/loop.py).

def sorted_fold_plan(pos: jnp.ndarray, num_rows: int):
    """Index plan of a fold over runs sorted by target row.

    ``pos``: [n] int32 target rows in [0, num_rows]; ``num_rows`` itself
    is the dump row of dropped items, which sort last and are never
    read. Returns ``(perm, offsets, starts)``: ``perm`` [n + 1] int32,
    the stable argsort of ``pos`` (the first arrival of a row is the
    first of its run) and behind it ``n``, the place of no item, which
    gathers as a zero; ``offsets`` [num_rows + 1] int32, run ``i`` being
    the sorted positions ``offsets[i] .. offsets[i + 1] - 1``;
    ``starts`` [n + 1] bool, set at the first position of every run,
    the zero behind the items being a run of its own."""
    from .device_sort import argsort_words
    n = pos.shape[0]
    with jax.named_scope("index_plan"):
        perm = jnp.concatenate([argsort_words([pos.astype(jnp.uint32)]),
                                jnp.full(1, n, jnp.int32)])
        counts = jnp.zeros(num_rows + 1, jnp.int32).at[pos].add(1)
        offsets = jnp.concatenate([
            jnp.zeros(1, jnp.int32),
            jnp.cumsum(counts[:num_rows], dtype=jnp.int32)])
        starts = jnp.zeros(n + 1, jnp.bool_).at[jnp.concatenate(
            [offsets, jnp.full(1, n, jnp.int32)])].set(True)
    return perm, offsets, starts


def _segmented_scan(x: jnp.ndarray, starts: jnp.ndarray,
                    op: Callable = jnp.add) -> jnp.ndarray:
    """Inclusive folds by ``op`` that begin anew at every set flag, in
    log2(n) shifted passes (Hillis-Steele: 1.5 ms for 2^21 binary64
    sums on a v5e, where ``lax.associative_scan`` takes 7.7 ms and four
    minutes to compile). Every partial result combines terms of one run
    only: no prefix is subtracted anywhere, so a small run beside a
    large one keeps its precision. The zeros shifted in at the front
    reach only rows ahead of the first flag, which belong to no run."""
    n = x.shape[0]
    flags = starts
    d = 1
    while d < n:
        shifted = jnp.concatenate(
            [jnp.zeros((d,) + x.shape[1:], x.dtype), x[:-d]])
        x = jnp.where(_bshape(flags, x), x, op(shifted, x))
        flags = flags | jnp.concatenate(
            [jnp.ones(d, jnp.bool_), flags[:-d]])
        d *= 2
    return x


def sorted_fold_sum(leaf: jnp.ndarray, plan) -> jnp.ndarray:
    """Per-row sums of ``leaf`` [n, ...] over the runs of ``plan``
    (:func:`sorted_fold_plan`): [num_rows, ...], zero where a row has
    no item. Integer sums are exact; float sums add each run's terms in
    arrival order, pairwise.

    A row with no item reads the zero that the plan keeps behind the
    items as a run of its own, so the column is handed on as a plain
    gather of sums, as the scatter handed on a column in memory. With a
    ``where(..., 0)`` as the last operation the consumer's arithmetic
    compiled otherwise than behind a column: XLA:CPU moves a multiply
    that follows into the select, can then no longer contract it with
    the add behind it, and a stitched chain rounded otherwise than the
    same operations dispatched one by one."""
    perm, offsets, starts = plan
    n = perm.shape[0] - 1
    with jax.named_scope("sorted_fold"):
        sums = _segmented_scan(
            jnp.take(leaf, perm, axis=0, mode="fill", fill_value=0),
            starts)
        last = jnp.where(offsets[1:] > offsets[:-1], offsets[1:] - 1, n)
        return jnp.take(sums, last, axis=0, mode="clip")


def sorted_fold_first(leaf: jnp.ndarray, plan):
    """The first arrival of every row off the same plan: ``(values
    [num_rows, ...], present [num_rows])``. The sort is stable, so the
    first of a run is the item that arrived first."""
    perm, offsets, _ = plan
    with jax.named_scope("sorted_fold"):
        first = jnp.take(perm, offsets[:-1], mode="clip")
        return (jnp.take(leaf, first, axis=0),
                offsets[1:] > offsets[:-1])


# ----------------------------------------------------------------------
# dense fold into a few rows (ReduceToIndex's 8-byte sums, small range)
# ----------------------------------------------------------------------
# Where the index range is a few rows, each output row is one masked
# reduction over the items, and all rows of a leaf are ONE reduce over
# a [columns, rows, n] shape that XLA fuses with the compare: the leaf is
# read once, nothing of that shape is written, and there is no sort, no
# histogram, no gather of n rows and no scan. The work grows with the
# rows, so it is the engine for a few of them only (api/ops/reduce.py
# ``DENSE_FOLD_ROWS``). Dropped items carry the dump row ``num_rows``,
# which no row matches.

class DenseFoldPlan(NamedTuple):
    """Index plan of a dense fold: what it reads of the index alone."""
    pos: jnp.ndarray      # [n] int32 target rows, ``num_rows`` = dropped
    first: jnp.ndarray    # [num_rows] int32 first arrival, n where none


def _row_hits(pos: jnp.ndarray, num_rows: int, cols: int) -> jnp.ndarray:
    """[cols, num_rows, n] bool, ``pos[i] == r``. Compared on the whole
    shape: a compare broadcast from [num_rows, n] is one that XLA:TPU
    writes to memory when both halves of a binary64 read it."""
    shape = (cols, num_rows, pos.shape[0])
    return jnp.broadcast_to(pos, shape) == jax.lax.broadcasted_iota(
        jnp.int32, shape, 1)


def dense_fold_plan(pos: jnp.ndarray, num_rows: int) -> DenseFoldPlan:
    """The first arrival of every row: the least ``i`` with ``pos[i] ==
    r``, one masked min over the items."""
    n = pos.shape[0]
    with jax.named_scope("dense_fold"):
        arrival = jax.lax.broadcasted_iota(jnp.int32, (1, num_rows, n), 2)
        first = jnp.min(jnp.where(_row_hits(pos, num_rows, 1), arrival, n),
                        axis=2)[0]
    return DenseFoldPlan(pos, first)


def dense_fold(leaf: jnp.ndarray, pos: jnp.ndarray, num_rows: int,
               op: str, identity) -> jnp.ndarray:
    """Per-row ``op`` ("sum" / "min" / "max") of ``leaf`` [n, ...] over
    the items whose ``pos`` is the row: [num_rows, ...] in the leaf's
    dtype, ``identity`` where a row has no item."""
    n, trail = leaf.shape[0], leaf.shape[1:]
    cols = int(np.prod(trail, dtype=np.int64))
    with jax.named_scope("dense_fold"):
        hit = _row_hits(pos, num_rows, cols)
        vals = jnp.broadcast_to(leaf.reshape(n, cols).T[:, None, :],
                                hit.shape)
        masked = jnp.where(hit, vals, jnp.asarray(identity, leaf.dtype))
        if op == "sum":
            # in the leaf's dtype: jnp.sum widens narrow integers
            out = jnp.sum(masked, axis=2, dtype=leaf.dtype)
        else:
            out = (jnp.min if op == "min" else jnp.max)(masked, axis=2)
        return out.T.reshape((num_rows,) + trail)


def dense_fold_first(leaf: jnp.ndarray, plan: DenseFoldPlan):
    """The first arrival of every row off a dense plan: ``(values
    [num_rows, ...], present [num_rows])``, a gather of ``num_rows``
    rows; zeros where a row has no item."""
    n = plan.pos.shape[0]
    with jax.named_scope("dense_fold"):
        # a plain take: ``_rows_at``'s u32[n, 2] view of a 64-bit leaf
        # would copy all n rows to read num_rows of them
        return (jnp.take(leaf, plan.first, axis=0, mode="fill",
                         fill_value=0),
                plan.first < n)
