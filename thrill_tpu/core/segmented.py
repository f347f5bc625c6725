"""Sort-based segmented aggregation: the device reduce engine.

The reference aggregates with linear-probing hash tables
(reference: thrill/core/reduce_pre_phase.hpp:94,
reduce_by_hash_post_phase.hpp:44, reduce_probing_hash_table.hpp:77).
Hash tables are a pointer-chasing CPU idiom; the TPU-native equivalent
is sort + segmented reduction: XLA's bitonic sort groups equal keys into
runs, a segmented associative scan combines each run with the user's
reduce function, and run representatives are compacted out. Everything
is static-shaped, branch-free and VPU/MXU friendly.
"""

from __future__ import annotations

from typing import Any, Callable, List, Tuple

import jax
import jax.numpy as jnp

from . import rowmove


def sort_by_key_words(words: List[jnp.ndarray], tree: Any, valid: jnp.ndarray,
                      extra_words: List[jnp.ndarray] = ()):
    """Stable sort of (words, tree, valid) with invalid items last.

    Returns (sorted_words, sorted_tree, sorted_valid). ``extra_words``
    sort after the key words (e.g. global index for stability).
    """
    invalid_first_word = (~valid).astype(jnp.uint32)  # valid(0) < invalid(1)
    sort_keys = [invalid_first_word] + list(words) + list(extra_words)
    perm = _argsort_multi(sort_keys)
    take = lambda x: jnp.take(x, perm, axis=0)
    # row movement by a permutation, like core/rowmove.py's: the same
    # name in a device profile
    with jax.named_scope(rowmove.SCOPE):
        return ([take(w) for w in words],
                jax.tree.map(take, tree),
                take(valid),
                [take(w) for w in extra_words])


def _argsort_multi(keys: List[jnp.ndarray]) -> jnp.ndarray:
    """Stable argsort by multiple uint64 key arrays (lexicographic)."""
    from .device_sort import argsort_words
    return argsort_words(keys)


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


def segmented_reduce(words: List[jnp.ndarray], tree: Any,
                     valid: jnp.ndarray, reduce_fn: Callable
                     ) -> Tuple[List[jnp.ndarray], Any, jnp.ndarray]:
    """Combine each equal-key run into one item.

    Inputs must be key-sorted with invalid items last. Returns
    (words, tree, rep_mask): ``rep_mask`` marks one surviving item per
    run, whose tree value is the fold of the whole run. The fold uses a
    segmented inclusive scan, so ``reduce_fn`` must be associative
    (same contract as the reference's reduce function).
    """
    starts = segment_boundaries(words, valid)

    def combine(a, b):
        tree_a, flag_a = a
        tree_b, flag_b = b
        merged = reduce_fn(tree_a, tree_b)
        keep_b = jax.tree.map(
            lambda m, vb: jnp.where(_bshape(flag_b, m), vb, m),
            merged, tree_b)
        return keep_b, flag_a | flag_b

    scanned, _ = jax.lax.associative_scan(combine, (tree, starts), axis=0)
    return words, scanned, _rep_mask(starts, valid)


def _rep_mask(starts: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """Representative = last item of each run (position before the next
    start), or the last valid item overall. Shared by both segmented
    reduce engines so the contract cannot diverge."""
    n = valid.shape[0]
    next_start = jnp.roll(starts, -1).at[-1].set(True)
    count = jnp.sum(valid.astype(jnp.int32))
    is_last_valid = jnp.arange(n) == count - 1
    return valid & (next_start | is_last_valid)


def _bshape(flag, leaf):
    """Broadcast [n] flag against leaf [n, ...]."""
    return flag.reshape(flag.shape + (1,) * (leaf.ndim - 1))


@jax.named_scope("segmented_reduce")
def reduce_runs(words, tree, valid, reduce_fn, specs):
    """One dispatch point for every device reduce program: the
    segment-op engine when ``specs`` (from FieldReduce, pre-gated by
    :func:`fields_specializable`) is available, else the generic
    associative scan. Same (words, tree, rep) contract either way."""
    if specs is not None:
        return segmented_reduce_fields(words, tree, valid, specs)
    return segmented_reduce(words, tree, valid, reduce_fn)


def fields_specializable(flat_specs, leaf_dtypes) -> bool:
    """Can :func:`segmented_reduce_fields` handle this FieldReduce
    spec? "first" takes any dtype; "sum" needs numeric (bool addition
    differs between numpy and the scan's `+`); "min"/"max" need
    INTEGER dtypes — float segment-min/max via scatter does not
    guarantee the NaN-propagation order jnp.minimum gives the generic
    scan, so floats keep the scan."""
    import numpy as np
    for s, dt in zip(flat_specs, leaf_dtypes):
        if s == "first":
            # bool/int/uint/float all route through an exact integer
            # segment_sum (floats via bitcast); complex has no clean
            # bitcast target — keep the scan for it
            if np.issubdtype(dt, np.complexfloating):
                return False
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


def segmented_reduce_fields(words: List[jnp.ndarray], tree: Any,
                            valid: jnp.ndarray, flat_specs
                            ) -> Tuple[List[jnp.ndarray], Any,
                                       jnp.ndarray]:
    """FieldReduce specialization of :func:`segmented_reduce` — same
    inputs and (words, tree, rep_mask) contract, different engine: each
    field folds with ONE sorted segment reduction plus one gather
    instead of the O(log n)-round associative scan over the whole tree.
    On TPU that is a single scatter pass per field through HBM rather
    than log2(n) combine rounds; the reference reaches the same shape
    by accumulating std::plus directly in its probing table.

    "first" is computed as segment_sum of a start-row-masked
    contribution (each segment receives exactly one addend — its first
    row — so the sum IS the first value, exactly). Caller gates with
    :func:`fields_specializable`.
    """
    import jax.ops as jops

    n = valid.shape[0]
    starts = segment_boundaries(words, valid)
    seg = jnp.clip(jnp.cumsum(starts.astype(jnp.int32)) - 1, 0, n - 1)
    leaves, td = jax.tree.flatten(tree)
    out_leaves = []
    for s, leaf in zip(flat_specs, leaves):
        v = _bshape(valid, leaf)
        if s == "first":
            st = _bshape(starts, leaf)
            # exactly one addend lands in each segment, so segment_sum
            # IS a select — but only over INTEGERS: bools cast through
            # int32, and floats BITCAST to same-width uints (a float
            # sum would canonicalize -0.0 + 0.0 to +0.0, silently
            # diverging from the scan engine on sign-bit-sensitive
            # consumers) and bitcast back
            fdt = leaf.dtype
            if fdt == jnp.bool_:
                src = leaf.astype(jnp.int32)
            elif jnp.issubdtype(fdt, jnp.floating):
                src = jax.lax.bitcast_convert_type(
                    leaf, jnp.dtype(f"uint{fdt.itemsize * 8}"))
            else:
                src = leaf
            contrib = jnp.where(st, src, jnp.zeros_like(src))
            res = jops.segment_sum(contrib, seg, num_segments=n,
                                   indices_are_sorted=True)
            if fdt == jnp.bool_:
                res = res.astype(jnp.bool_)
            elif jnp.issubdtype(fdt, jnp.floating):
                res = jax.lax.bitcast_convert_type(res, fdt)
        elif s == "sum":
            # Float sums mask invalid rows to +0.0, which IEEE adds
            # as identity EXCEPT for the sign of zero: a group whose
            # true sum is -0.0 comes back +0.0 here (the scan engine,
            # folding only real rows, preserves -0.0). Accepted
            # divergence — the unordered-reduce contract never
            # promised sign-of-zero, and excluding float sums would
            # forfeit the specialization for the dominant use case.
            contrib = jnp.where(v, leaf, jnp.zeros_like(leaf))
            res = jops.segment_sum(contrib, seg, num_segments=n,
                                   indices_are_sorted=True)
        elif s == "min":
            fill = jnp.array(jnp.iinfo(leaf.dtype).max, leaf.dtype)
            contrib = jnp.where(v, leaf, fill)
            res = jops.segment_min(contrib, seg, num_segments=n,
                                   indices_are_sorted=True)
        else:  # "max"
            fill = jnp.array(jnp.iinfo(leaf.dtype).min, leaf.dtype)
            contrib = jnp.where(v, leaf, fill)
            res = jops.segment_max(contrib, seg, num_segments=n,
                                   indices_are_sorted=True)
        out_leaves.append(jnp.take(res, seg, axis=0))
    return (words, jax.tree.unflatten(td, out_leaves),
            _rep_mask(starts, valid))


# ----------------------------------------------------------------------
# fold over runs sorted by a dense index (ReduceToIndex's 8-byte sums)
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


def _segmented_cumsum(x: jnp.ndarray, starts: jnp.ndarray) -> jnp.ndarray:
    """Inclusive sums that begin anew at every set flag, in log2(n)
    shifted passes (Hillis-Steele: 1.5 ms for 2^21 binary64 values on a
    v5e, where ``lax.associative_scan`` takes 7.7 ms and four minutes to
    compile). Every partial sum adds terms of one run only: no prefix
    is subtracted anywhere, so a small run beside a large one keeps its
    precision."""
    n = x.shape[0]
    flags = starts
    d = 1
    while d < n:
        shifted = jnp.concatenate(
            [jnp.zeros((d,) + x.shape[1:], x.dtype), x[:-d]])
        x = jnp.where(_bshape(flags, x), x, shifted + x)
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
        sums = _segmented_cumsum(
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
