"""ReduceByKey / ReducePair / ReduceToIndex.

Reference: thrill/api/reduce_by_key.hpp:64 (two-phase hash aggregation:
pre-phase table partitioned by worker, stream shuffle, post-phase table)
and reduce_to_index.hpp:60 (range-partitioned dense variant).

TPU-native design: both phases are sort+segmented-reduce device programs
(see core/segmented.py) around a hash- or range-partitioned all-to-all
exchange — pre-reduction cuts shuffle volume exactly like the reference's
pre-phase table, and the whole pipeline is three jitted SPMD programs.
Host storage falls back to dict-based aggregation per worker (the same
algorithm the reference runs, in Python).

ReduceToIndex's dense local phase has four engines, chosen from what
the code can see of the reduce function, the leaves' specs and dtypes,
and the static padded range ``out_cap``:

- declarative ``FieldReduce`` specs over leaves of 4 bytes or fewer:
  one ``.at[row].add/min/max`` per field, no sort (6.8 ns per update on
  a v5e; ``_scatter_reduce_apply``);
- a ``"sum"`` of 8-byte leaves in such a tree, into at most
  ``DENSE_FOLD_ROWS`` rows: the dense fold, one masked reduction per row
  over every leaf in one pass (core/segmented.py ``dense_fold*``);
- the same into more rows: the fold over runs sorted by row
  (core/segmented.py ``sorted_fold_*``), because XLA:TPU scatters
  8-byte values as pairs at 122-126 ns per update;
- any other reduce function: sort the items by index and fold the
  runs (``sort_by_key_words`` + ``reduce_runs``, which hands back one
  row per run, compact and in index order), then scatter those rows to
  their dense places by the gathered index word.

The two folds read an index plan (the sorted fold's permutation and
runs; the dense fold's first arrivals, where a field needs them) that
reads no value, so a loop with an invariant index computes it once
(api/fusion.py ``Segment.index_plan``).
"""

from __future__ import annotations

from typing import Any, Callable, List, Optional

import jax
import jax.numpy as jnp
from jax import lax
import numpy as np

from ...common import hashing
from ...common.partition import dense_range_bounds
from ...core import keys as keymod
from ...core import segmented
from ...data import exchange
from ...data.shards import DeviceShards, HostShards
from ..dia import DIA
from ..dia_base import DIABase
from ...parallel.mesh import AXIS

# the name ReduceToIndex's FieldReduce engines carry in a device profile
# (jax.named_scope: HLO metadata, no operation added); the folds of
# 8-byte sums nest ``index_plan`` and ``sorted_fold``, or ``dense_fold``,
# under it
SCATTER_SCOPE = "reduce_to_index"


# device DuplicateDetection registers are sized per site by
# core/preshuffle.register_width (collisions only cause unnecessary
# shuffling, never wrong results)


def _device_fold_specs(reduce_fn, treedef, leaves):
    """Flat FieldReduce specs when the DEVICE per-field specialization
    applies (core/segmented.py segmented_reduce_fields), else None."""
    from ..functors import FieldReduce
    if not isinstance(reduce_fn, FieldReduce):
        return None
    specs = reduce_fn.flat_spec(treedef)
    if specs is None or not segmented.fields_specializable(
            specs, [l.dtype for l in leaves]):
        return None
    return specs


def _local_reduce_device(shards: DeviceShards, key_fn: Callable,
                         reduce_fn: Callable, phase: str,
                         token) -> DeviceShards:
    """One jitted program: encode keys, sort, fold the runs (one row per
    run, compact as it comes: core/segmented.py ``reduce_runs``)."""
    mex = shards.mesh_exec
    # an optimistic post-exchange input may owe its capacity check —
    # heal before reading the columns (data/exchange.py)
    shards.validate_pending()
    out = _host_reduce_shards(shards, key_fn, reduce_fn)
    if out is not None:
        return out
    cap = shards.cap
    leaves, treedef = jax.tree.flatten(shards.tree)
    specs = _device_fold_specs(reduce_fn, treedef, leaves)
    key = ("reduce_local", phase, token, cap, treedef,
           tuple((l.dtype, l.shape[2:]) for l in leaves))

    def build():
        def f(counts_dev, *ls):
            valid = jnp.arange(cap) < counts_dev[0, 0]
            tree = jax.tree.unflatten(treedef, [l[0] for l in ls])
            words = keymod.encode_key_words(key_fn(tree))
            words, tree, valid, _ = segmented.sort_by_key_words(
                words, tree, valid)
            _, tree, n_runs = segmented.reduce_runs(
                words, tree, valid, reduce_fn, specs)
            out_leaves = jax.tree.leaves(tree)
            return (n_runs[None, None].astype(jnp.int32),
                    *[l[None] for l in out_leaves])

        return mex.smap(f, 1 + len(leaves))

    fn = mex.cached(key, build)
    out = fn(shards.counts_device(), *leaves)
    tree = jax.tree.unflatten(treedef, list(out[1:]))
    # counts stay on device: pre-phase -> exchange phase A dispatches
    # back-to-back with no host sync in between
    return DeviceShards(mex, tree, out[0])


def _host_reduce_shards(shards: DeviceShards, key_fn: Callable,
                        reduce_fn: Callable) -> Optional[DeviceShards]:
    """CPU-backend mirror of :func:`_local_reduce_device`: native
    hash-grouping (core/host_radix.py) + a strided in-place run fold.

    On the CPU backend device buffers are host memory and XLA's
    single-core sort + associative_scan are the wrong engines (a 1.2M
    row WordCount reduce spent ~17s there). Grouping uses the native
    open-addressing table (ONE pass; the engine class of the
    reference's ReducePrePhase, thrill/core/reduce_pre_phase.hpp:94)
    rather than the radix argsort — ReduceByKey only needs equal keys
    adjacent, not sorted. The fold then combines each group to its head
    row in log2(longest run) vectorized ``reduce_fn`` calls (same
    associativity contract as the device segmented scan) with a total
    gathered-row volume of ~1n (see :func:`_strided_run_fold`).

    Returns None when inapplicable (non-CPU, multi-controller, trace-
    only key_fn) so the caller falls through to the jitted engine."""
    from ...core import host_radix

    mex = shards.mesh_exec
    if not host_radix.eligible(mex):
        return None
    leaves, treedef = jax.tree.flatten(shards.tree)
    leaves_np = [np.asarray(l) for l in leaves]          # [W, cap, ...]
    W = mex.num_workers
    out_counts = np.zeros(W, dtype=np.int64)
    per_worker = []
    # any failure (trace-only key_fn, a reduce_fn using jax-array-only
    # APIs like .at[] on the numpy trees, ...) falls back to the jitted
    # engine, which either handles it or raises the real error
    try:
        for w in range(W):
            cnt = int(shards.counts[w])
            tree = jax.tree.unflatten(treedef,
                                      [l[w][:cnt] for l in leaves_np])
            if cnt == 0:
                per_worker.append(tree)
                continue
            words = keymod.encode_key_words_np(key_fn(tree))
            fused = _fused_field_reduce(tree, treedef, words, reduce_fn)
            if fused is not None:
                tree, ngroups = fused
            else:
                perm, lens = host_radix.hash_group(words)
                tree = jax.tree.map(
                    lambda a: host_radix.gather_rows(
                        np.ascontiguousarray(a), perm), tree)
                # identity write-back skip is only sound for functors
                # known pure; a black-box reduce_fn may mutate its
                # left argument in place and return it
                from ..functors import FieldReduce
                tree = _strided_run_fold(
                    tree, lens, reduce_fn,
                    allow_identity_skip=isinstance(reduce_fn, FieldReduce))
                ngroups = len(lens)
            per_worker.append(tree)
            out_counts[w] = ngroups
    except host_radix.NativeEngineError:
        # the native engine itself is broken (bad rc / plan mismatch) —
        # not an inapplicable-input case. Warn loudly before falling
        # back so a real bug doesn't masquerade as slowness.
        import warnings
        import traceback
        warnings.warn("native reduce engine failed; falling back to the "
                      "jitted engine:\n" + traceback.format_exc(),
                      RuntimeWarning)
        return None
    except Exception:
        return None
    return DeviceShards.from_worker_arrays(mex, per_worker,
                                           counts=out_counts)


def _fused_field_reduce(tree, treedef, words, reduce_fn):
    """FieldReduce fast path: when the reduce functor is declarative
    (api/functors.py) and every accumulated leaf is a supported scalar
    column, the ENTIRE local reduction runs as one native hash-probe
    pass (hash_group_acc_u64) — grouping and accumulation fused, no
    permutation/gather/fold afterwards. This is the runtime analog of
    the reference's templates inlining the functor into the probing
    table (thrill/core/reduce_pre_phase.hpp:94). Returns
    ``(out_tree, ngroups)`` or None to fall back to the generic fold."""
    from ..functors import FieldReduce, acc_plan
    from ...core import host_radix

    if not isinstance(reduce_fn, FieldReduce):
        return None
    specs = reduce_fn.flat_spec(treedef)
    if specs is None:
        return None
    leaves = jax.tree.leaves(tree)
    plans = []
    for s, a in zip(specs, leaves):
        p = acc_plan(s, a.dtype, a.ndim)
        if p is None:
            return None
        plans.append(p)
    cols, ops = [], []
    for (opcode, conv), a in zip(plans, leaves):
        if opcode < 0:
            continue                       # "first": gathered below
        ops.append(opcode)
        cols.append(a.astype(conv, copy=False))
    heads, accs = host_radix.hash_group_acc(words, cols, ops)
    out_leaves, ai = [], 0
    for (opcode, conv), a in zip(plans, leaves):
        if opcode < 0:
            out_leaves.append(
                host_radix.gather_rows(np.ascontiguousarray(a), heads))
        else:
            acc = accs[ai]
            ai += 1
            out_leaves.append(acc if acc.dtype == a.dtype
                              else acc.astype(a.dtype))
    return jax.tree.unflatten(treedef, out_leaves), len(heads)


def _strided_run_fold(tree, lens: np.ndarray, reduce_fn: Callable,
                      allow_identity_skip: bool = False):
    """Fold each contiguous run of group-clustered rows into its head
    row, in place, then gather the heads.

    Classic power-of-two strided up-sweep over stable row indices: the
    row at in-run position p > 0 is absorbed exactly once, at step
    s = p & -p, into the row s slots left of it (which by then holds
    the fold of positions [p-s, p)), so after all steps each run head
    holds the left-to-right fold of its whole run. Compared to a
    compact-every-level scheme this needs NO per-level position
    recomputation (the native ``fold_plan`` emits all per-level index
    lists in one O(n) pass) and no whole-tree compaction per level:
    total gathered+scattered rows across all levels is exactly
    3*(n - num_runs) plus one final head gather. ``reduce_fn`` sees
    (left_rows, right_rows) with left rows earlier in the run, so
    non-commutative (associative) functions are safe.

    MUTATES the leaves of ``tree`` (callers pass freshly gathered
    arrays). Returns the head-compacted tree (len(lens) rows)."""
    from ...core import host_radix

    leaves, td = jax.tree.flatten(tree)
    leaves = [np.ascontiguousarray(a) for a in leaves]
    ri_all, level_counts = host_radix.fold_plan(lens)
    off = 0
    for lvl in range(32):
        lc = int(level_counts[lvl])
        if lc == 0:
            continue
        ri = ri_all[off:off + lc]
        off += lc
        li = (ri - np.uint32(1 << lvl)).astype(np.uint32, copy=False)
        left = jax.tree.unflatten(
            td, [host_radix.gather_rows(a, li) for a in leaves])
        right = jax.tree.unflatten(
            td, [host_radix.gather_rows(a, ri) for a in leaves])
        left_leaves = jax.tree.leaves(left)
        merged = reduce_fn(left, right)
        if jax.tree.structure(merged) != td:
            # positional zip below would silently scatter mispaired
            # leaves; a malformed reduce_fn must be a hard error (the
            # jitted engine's tree.map raises on this too)
            raise ValueError(
                f"reduce_fn returned tree structure "
                f"{jax.tree.structure(merged)} != item structure {td}")
        for a, m, ll in zip(leaves, jax.tree.leaves(merged), left_leaves):
            if allow_identity_skip and m is ll:
                # a PURE functor (FieldReduce "first") passed the left
                # rows through unchanged: scattering a[li] back to
                # a[li] is a no-op. Gated on provable purity — a
                # black-box reduce_fn returning `m is ll` may have
                # MUTATED the gathered left leaf in place, and its
                # merged values must still be written back.
                continue
            host_radix.scatter_rows(
                a, li, np.ascontiguousarray(np.asarray(m), dtype=a.dtype))
    starts = np.zeros(len(lens), dtype=np.uint32)
    np.cumsum(lens[:-1], dtype=np.uint32, out=starts[1:])
    return jax.tree.unflatten(
        td, [host_radix.gather_rows(a, starts) for a in leaves])


class ReduceNode(DIABase):
    # both phase tables want workspace (reference: ReduceByKey registers
    # DIAMemUse::Max for its pre/post tables, api/reduce_by_key.hpp);
    # the host path sizes its EM tables from the grant, the device path
    # bounds memory by construction and leaves the grant unused
    MEM_USE = "max"

    def __init__(self, ctx, link, key_fn: Callable, reduce_fn: Callable,
                 label: str = "ReduceByKey",
                 dup_detection=None, token=None) -> None:
        super().__init__(ctx, label, [link])
        self.key_fn = key_fn
        self.reduce_fn = reduce_fn
        # executable-cache token. When a wrapper (ReducePair) mints
        # fresh closures per call, it must pass a token derived from
        # the USER's stable functions, or loops recompile every
        # iteration.
        self.token = token if token is not None else (key_fn, reduce_fn)
        # reference: DuplicateDetectionTag, api/reduce_by_key.hpp — skip
        # shuffling keys whose hash is globally unique. None = decided
        # by the plan-time cost model (core/preshuffle.py)
        self.dup_detection = dup_detection

    def _fuse_segment(self, phase: str):
        """This node's local combine phase as a fused segment
        (api/fusion.py): the same encode + sort + fold trace as
        :func:`_local_reduce_device`, stitched into a larger program
        instead of paying its own dispatch. The fold's rows are a
        prefix already, so no compaction follows it. The FieldReduce
        specs are derived at trace time from the actual traced tree
        (the composite plan key pins treedef/dtypes, so the choice is
        deterministic per executable)."""
        from ...core import host_radix
        from .. import fusion
        if host_radix.eligible(self.context.mesh_exec):
            return None      # the native CPU engine beats the jitted one
        key_fn, reduce_fn = self.key_fn, self.reduce_fn

        def trace(fctx, tree, mask, _bound):
            leaves, td = jax.tree.flatten(tree)
            specs = _device_fold_specs(reduce_fn, td, leaves)
            words = keymod.encode_key_words(key_fn(tree))
            words, tree_s, valid, _ = segmented.sort_by_key_words(
                words, tree, mask)
            _, tree_k, n_runs = segmented.reduce_runs(
                words, tree_s, valid, reduce_fn, specs)
            return tree_k, jnp.arange(mask.shape[0]) < n_runs

        return fusion.Segment(label="ReduceLocal",
                              token=("reduce_local", phase, self.token),
                              trace=trace, already_compact=True,
                              dia_id=self.id)

    def compute_plan(self):
        from .. import fusion
        plan = fusion.pull_plan(self.parents[0])
        seg = self._fuse_segment("pre") if plan.stitchable else None
        if seg is None:
            return fusion.wrap(self._compute_on(plan.finish()))
        plan.append(seg)
        if self.context.num_workers == 1:
            # the pre-phase IS the whole reduce at W == 1: hand the
            # plan on so downstream ops stitch onto it
            return plan
        # finish(), not execute(): the exchange below is a fusion
        # barrier consuming the columns — pending checks drain first
        pre = plan.finish()
        return self._post_exchange(pre)

    def compute(self):
        plan = self.compute_plan()
        return plan.finish()

    def _compute_on(self, shards):
        """Pre-fusion compute body over pulled shards (the
        THRILL_TPU_FUSE=0 path, and the host/native fallbacks)."""
        if isinstance(shards, HostShards):
            return self._compute_host(shards)
        key_fn, reduce_fn = self.key_fn, self.reduce_fn
        token = self.token
        W = self.context.num_workers
        # pre-phase: local combine (reference: ReducePrePhase)
        pre = _local_reduce_device(shards, key_fn, reduce_fn, "pre", token)
        if W == 1:
            # the pre-phase already combined every key; with no
            # exchange there is nothing for a post phase to merge
            return pre
        return self._post_exchange(pre).finish()

    def _post_exchange(self, pre: "DeviceShards"):
        """Shuffle the pre-reduced shards and run the post combine.
        Returns a FusionPlan (post phase pending when fusible, so
        downstream ops can stitch onto it)."""
        from .. import fusion
        key_fn, reduce_fn = self.key_fn, self.reduce_fn
        token = self.token
        W = self.context.num_workers
        mex = self.context.mesh_exec
        dup = self.dup_detection
        if dup is None and W > 1:
            # plan-time cost model (core/preshuffle.py): presence-
            # register psum bytes vs the pre-reduced rows expected to
            # stay local. The pre-phase cap is globally agreed, so the
            # verdict is deterministic across controllers.
            from ...core import preshuffle
            import jax as _jax
            item_bytes = exchange.leaf_item_bytes(
                _jax.tree.leaves(pre.tree))
            dup = preshuffle.auto_dup_detect(
                mex, pre.cap * W, item_bytes, ("reduce_dup", token))
        dup = bool(dup)
        # shuffle by key hash (reference: Mix/CatStream exchange).
        # With DuplicateDetection, globally-unique key hashes skip the
        # shuffle: a register psum inside the destination program finds
        # hashes held by exactly one worker and keeps those items local
        # (reference: core/duplicate_detection.hpp:46 — the Golomb-coded
        # register exchange becomes one psum over a [M] register array).
        if W > 1:
            if dup:
                from ...core import preshuffle
                M = preshuffle.register_width(pre.cap * W)
            else:
                M = 0

            def dest(tree, mask, widx):
                words = keymod.encode_key_words(key_fn(tree))
                h = hashing.hash_key_words(words)
                hash_dest = (h % jnp.uint64(W)).astype(jnp.int32)
                if not dup:
                    return hash_dest
                reg = (h % jnp.uint64(M)).astype(jnp.int32)
                # presence (not item counts): a worker contributes 0/1
                # per register, so the psum'd holder count fits u8 for
                # W < 256 — a quarter of the i32 registers' fabric
                # bytes, same verdict ("exactly one worker holds this
                # hash, and it is me"). Wider meshes keep i32: a u8
                # psum would WRAP (257 holders reads as 1) and silently
                # keep colliding keys local — wrong results, not just
                # extra traffic.
                reg_dt = jnp.uint8 if W < 256 else jnp.int32
                # the registers' fill, psum and takes, for a profile
                with jax.named_scope("reduce_by_key"), \
                        jax.named_scope("dup_detect"):
                    if reg_dt == jnp.uint8:
                        # register fill through the Pallas presence
                        # kernel where it engages (bit-identical:
                        # presence is 0/1)
                        from ...core.pallas_kernels import presence_fill
                        local = presence_fill(reg, mask, M)
                    else:
                        local = jnp.zeros(M, reg_dt).at[reg].max(
                            mask.astype(reg_dt))
                    holders = lax.psum(local, AXIS)
                    mine_only = (jnp.take(holders, reg) == 1) & \
                        (jnp.take(local, reg) == 1)
                return jnp.where(mine_only, widx.astype(jnp.int32),
                                 hash_dest)

            if dup:
                mex.stats_dup_detect_exchanges += 1
            pre = exchange.exchange(pre, dest,
                                    ("reduce_dest", token, W, dup),
                                    span_fields={"dup": dup, "regs": M})
        # post-phase: final combine (reference: ReduceByHashPostPhase);
        # fusible, so the chain continues across the exchange barrier
        if fusion.enabled():
            seg = self._fuse_segment("post")
            if seg is not None:
                plan = fusion.FusionPlan(pre.mesh_exec, [pre])
                plan.append(seg)
                return plan
        return fusion.wrap(
            _local_reduce_device(pre, key_fn, reduce_fn, "post", token))

    def _compute_host(self, shards: HostShards):
        W = shards.num_workers
        mex = self.context.mesh_exec
        key_fn, reduce_fn = self.key_fn, self.reduce_fn
        from ...core.em_table import EMReduceTable
        from ...data import multiplexer
        from ...data.block_pool import spill_pool
        owns_input = self.parents[0].node.state == "DISPOSED"
        # pre-phase per worker (local combine cuts shuffle volume, the
        # reference's ReducePrePhase table). Deliberately NOT
        # grant-flushed: the input it folds is already RAM-resident, so
        # the table's footprint is bounded by the input itself (at most
        # one folded aggregate per distinct key), while flushing
        # partials to the outgoing list — the in-RAM analog of the
        # reference's flush-to-NETWORK (core/reduce_pre_phase.hpp) —
        # would regress high-duplication workloads from O(distinct) to
        # O(items) decorated tuples in RAM and on the wire (round-5
        # review). The grant-bounded EM machinery lives in the POST
        # phase below, where spills leave RAM for the block store.
        pre_entries: List[list] = []      # per worker: [(k, v), ...]
        for lst in shards.lists:
            table: dict = {}
            for it in lst:
                k = key_fn(it)
                table[k] = reduce_fn(table[k], it) if k in table else it
            pre_entries.append(list(table.items()))
            if owns_input:
                lst.clear()       # spill-free analog of Sort's release
        # one hash per entry, computed once and carried with the item
        # through detection, keep-check and the shuffle dest
        pre_hashes = [[hashing.stable_host_hash(k) for k, _ in entries]
                      for entries in pre_entries]
        non_unique = None
        dup = self.dup_detection
        if dup is None:
            # host path: exact local entry counts feed the cost model
            # (local_rows: multi-controller runs all-reduce them to
            # the global count before deciding, core/preshuffle.py)
            from ...core import preshuffle
            rows = sum(len(h) for h in pre_hashes)
            dup = preshuffle.auto_dup_detect(
                mex, rows, 32, ("reduce_dup_host", self.token),
                local_rows=True)
        if dup and W > 1:
            from ...core import duplicate_detection as dd
            hash_lists = pre_hashes
            if multiplexer.multiprocess(mex):
                # fingerprint exchange over the control plane: ship the
                # hashes (not the items) so every process agrees on the
                # globally-unique set (reference:
                # core/duplicate_detection.hpp:46)
                local = {w: hash_lists[w] for w in mex.local_workers}
                merged = [[] for _ in range(W)]
                for msg in mex.host_net.all_gather(local):
                    for w, hs in msg.items():
                        merged[int(w)] = hs
                hash_lists = merged
            non_unique = dd.find_non_unique_hashes(hash_lists)
        # shuffle + post-phase; globally-unique keys stay local. Items
        # travel as (src_worker_kept, hash, key, value) so the
        # PRE-PHASE key stays authoritative (reduce_fn need not
        # preserve key_fn — the reference's tables likewise carry the
        # extracted key) and the precomputed hash rides along instead
        # of being recomputed per routing decision.
        def dest(kv):
            keep, h, _, _ = kv
            if keep is not None:
                return keep
            return h % W

        pre_lists = []
        for w, entries in enumerate(pre_entries):
            hs = pre_hashes[w]
            lst = []
            for (k, v), h in zip(entries, hs):
                keep = None
                if non_unique is not None and dd.is_unique(h, non_unique):
                    keep = w              # globally unique: stays local
                lst.append((keep, h, k, v))
            entries.clear()
            pre_lists.append(lst)
        del pre_entries, pre_hashes
        # hash-partition target: the post-phase reduce table is keyed,
        # so batch ARRIVAL order is semantically free — under
        # THRILL_TPU_HOST_MIX=1 delivery is MixStream (arrival order;
        # note a non-commutative float reduce_fn then folds in that
        # order — the documented contract for opting in)
        ex = multiplexer.host_exchange(mex, HostShards(W, pre_lists),
                                       dest, reason="reduce",
                                       rank_order=False)
        # post-phase: EM reduce tables sized by the grant — spilled
        # partitions re-reduce recursively, so distinct keys beyond the
        # grant stream through bounded RAM (reference:
        # core/reduce_by_hash_post_phase.hpp:44-120)
        pool = spill_pool(self.context.config.spill_dir,
                          self.mem_limit)
        stats: dict = {}
        post_lists = []
        try:
            for items in ex.lists:
                t = EMReduceTable(reduce_fn, pool, self.mem_limit,
                                  stats=stats or None)
                stats = t.stats
                for _, h, k, v in items:
                    t.insert(k, v, h)
                items.clear()    # exchange output is ours: free as we go
                post_lists.append(list(t.emit()))
                t.close()
        finally:
            pool.close()
        self._em_stats = stats
        if stats.get("spills") and self.context.logger.enabled:
            self.context.logger.line(event="reduce_post_spill",
                                     node=self.label, dia_id=self.id,
                                     **stats)
        return HostShards(W, post_lists)


def ReduceByKey(dia: DIA, key_fn: Callable, reduce_fn: Callable,
                dup_detection=None) -> DIA:
    return DIA(ReduceNode(dia.context, dia._link(), key_fn, reduce_fn,
                          dup_detection=dup_detection))


def ReducePair(dia: DIA, value_reduce_fn) -> DIA:
    """Items are (key, value) pairs; combine values of equal keys.
    Reference: ReducePair, api/reduce_by_key.hpp.

    ``value_reduce_fn`` may be a callable, or a declarative op string
    ("sum"/"min"/"max") — the spelling of the reference's common
    functors (std::plus, common::minimum) that unlocks the fused
    native aggregation path (api/functors.py FieldReduce)."""
    def key_fn(kv):
        return kv[0]

    if isinstance(value_reduce_fn, str):
        from ..functors import FieldReduce
        red = FieldReduce(("first", value_reduce_fn))
        # token carries the content-hashed functor, NOT the per-call
        # key_fn closure — identical specs share compiled executables
        return DIA(ReduceNode(dia.context, dia._link(), key_fn, red,
                              label="ReducePair",
                              token=("ReducePair", red)))

    def reduce_fn(a, b):
        return (a[0], value_reduce_fn(a[1], b[1]))

    return DIA(ReduceNode(dia.context, dia._link(), key_fn, reduce_fn,
                          label="ReducePair",
                          token=("ReducePair", value_reduce_fn)))


def _host_reduce_to_index(shards: DeviceShards, index_fn, reduce_fn,
                          bounds: np.ndarray, neutral):
    """CPU-backend mirror of ReduceToIndex's dense scatter-reduce (the
    same engine-selection argument as :func:`_host_reduce_shards`).

    FieldReduce specs run as numpy ufunc.at scatter-accumulations per
    column (no grouping pass at all); generic reduce functions group
    via the native hash table + strided fold, then scatter group heads
    by index. Unset indices fill with ``neutral`` (zeros when None,
    matching the device program's zero base). Returns None when
    inapplicable."""
    from ...core import host_radix
    from ..functors import FieldReduce, acc_plan

    mex = shards.mesh_exec
    if not host_radix.eligible(mex):
        return None
    leaves, treedef = jax.tree.flatten(shards.tree)
    leaves_np = [np.asarray(l) for l in leaves]
    W = mex.num_workers
    local_sizes = (bounds[1:] - bounds[:-1]).astype(np.int64)
    neutral_leaves = None
    if neutral is not None:
        if jax.tree.structure(neutral) != treedef:
            # positional pairing below would silently mismatch fields;
            # the jitted engine raises loudly on this — let it
            return None
        neutral_leaves = jax.tree.leaves(neutral)
    specs = None
    if isinstance(reduce_fn, FieldReduce):
        specs = reduce_fn.flat_spec(treedef)
        if specs is not None:
            for s, a in zip(specs, leaves_np):
                if s == "first":
                    continue         # any shape scatters fine
                # ufunc.at path needs 1-D numeric columns for the
                # accumulated fields (per-worker ndim = a.ndim - 1)
                if acc_plan(s, a.dtype, a.ndim - 1) is None:
                    specs = None
                    break
    per_worker = []
    try:
        for w in range(W):
            cnt = int(shards.counts[w])
            lo = int(bounds[w])
            size = int(local_sizes[w])
            tree = jax.tree.unflatten(treedef,
                                      [l[w][:cnt] for l in leaves_np])
            cols = jax.tree.leaves(tree)
            idx = (np.asarray(index_fn(tree)).astype(np.int64) - lo
                   if cnt else np.zeros(0, np.int64))
            if cnt and (idx.min() < 0 or idx.max() >= size):
                return None          # out-of-range: let the jitted
                                     # engine's clip semantics apply
            present = np.zeros(size, dtype=bool)
            present[idx] = True
            out_leaves = []
            if specs is not None:
                for s, col in zip(specs, cols):
                    out_leaves.append(
                        _scatter_field(s, col, idx, size))
            else:
                if cnt:
                    perm, lens = host_radix.hash_group(
                        [idx.astype(np.uint64)])
                    gtree = jax.tree.map(
                        lambda a: host_radix.gather_rows(
                            np.ascontiguousarray(a), perm), tree)
                    gtree = _strided_run_fold(
                        gtree, lens, reduce_fn,
                        allow_identity_skip=isinstance(reduce_fn,
                                                       FieldReduce))
                    starts = np.zeros(len(lens), dtype=np.uint32)
                    np.cumsum(lens[:-1], dtype=np.uint32,
                              out=starts[1:])
                    gidx = idx[perm[starts]]
                    for col in jax.tree.leaves(gtree):
                        base = np.zeros((size,) + col.shape[1:],
                                        col.dtype)
                        base[gidx] = col
                        out_leaves.append(base)
                else:
                    out_leaves = [np.zeros((size,) + a.shape[2:],
                                           a.dtype) for a in leaves_np]
            # fill indices no item mapped to: the neutral value, or 0
            # (the device program's zero scatter base) — ALWAYS applied
            # so min/max sentinel fills never leak into the output
            for i, ol in enumerate(out_leaves):
                nv = (neutral_leaves[i] if neutral_leaves is not None
                      else 0)
                ol[~present] = nv
            per_worker.append(jax.tree.unflatten(treedef, out_leaves))
    except host_radix.NativeEngineError:
        # same loud-fallback policy as _host_reduce_shards: a broken
        # native engine must not masquerade as slowness
        import traceback
        import warnings
        warnings.warn("native ReduceToIndex engine failed; falling "
                      "back to the jitted engine:\n"
                      + traceback.format_exc(), RuntimeWarning)
        return None
    except Exception:
        return None
    return DeviceShards.from_worker_arrays(mex, per_worker,
                                           counts=local_sizes)


def _scatter_field(op: str, col: np.ndarray, idx: np.ndarray,
                   size: int) -> np.ndarray:
    """One FieldReduce column as a dense scatter-accumulate."""
    if op == "first":
        out = np.zeros((size,) + col.shape[1:], col.dtype)
        # reversed assignment: the FIRST occurrence wins
        out[idx[::-1]] = col[::-1]
        return out
    out = np.zeros(size, col.dtype)
    if op == "sum":
        np.add.at(out, idx, col)
        return out
    if op == "min":
        out.fill(_type_max(col.dtype))
        np.minimum.at(out, idx, col)
    else:
        out.fill(_type_min(col.dtype))
        np.maximum.at(out, idx, col)
    # untouched slots hold sentinels; the caller's neutral fill (or the
    # zero default) overwrites them via the presence mask
    return out


def _type_max(dt):
    return (np.inf if np.issubdtype(dt, np.floating)
            else np.iinfo(dt).max)


def _type_min(dt):
    return (-np.inf if np.issubdtype(dt, np.floating)
            else np.iinfo(dt).min)


def _scatter_fold_specs(reduce_fn, treedef, leaves):
    """Flat FieldReduce specs when the SORT-FREE dense scatter engine
    applies to every leaf (ReduceToIndex only): "sum"/"min"/"max" need
    numeric non-bool leaves, "first" works for any dtype (scatter-min
    arbitration over arrival order + one gather). Returns None when any
    leaf must go through the sorted segmented engine instead."""
    from ..functors import FieldReduce
    if not isinstance(reduce_fn, FieldReduce):
        return None
    specs = reduce_fn.flat_spec(treedef)
    if specs is None:
        return None
    for s, l in zip(specs, leaves):
        if s != "first" and (l.dtype == jnp.bool_
                             or not (jnp.issubdtype(l.dtype, jnp.number))):
            return None
    return specs


def _dense_pos(valid, local_idx, range_size, out_cap):
    """Target row of every item, [cap] int32: its range-relative index,
    or the dump row ``out_cap`` for masked and out-of-range items."""
    ok = valid & (local_idx >= 0) & (local_idx < range_size)
    return jnp.where(ok, local_idx, out_cap).astype(jnp.int32)


def _run_pos(index_word, n_runs, range_start, out_cap):
    """Target row of every folded run (the generic engine's compact
    rows, core/segmented.py ``reduce_runs``), [cap] int: the run's
    index relative to the range, clipped into it, or the dump row
    ``out_cap`` for the rows behind the last run."""
    local_idx = index_word.astype(jnp.int64) - range_start
    live = jnp.arange(index_word.shape[0]) < n_runs
    return jnp.clip(jnp.where(live, local_idx, out_cap), 0, out_cap)


# a tree with a "sum" of 8-byte values folds densely, by one masked
# reduction per output row (core/segmented.py ``dense_fold``), where its
# padded range has at most this many rows, and over sorted runs where it
# has more. On a v5e, 2^22 rows of k-means' tree (binary64 [n, 3] and
# [n] sums, an int64 "first"): dense 3.8 / 8.6 / 14.5 / 27.7 / 105.2 ms
# at 16 / 64 / 128 / 256 / 1,024 rows, sorted 176.4-176.8 ms at each
# (PERF.md section 7, item 26)
DENSE_FOLD_ROWS = 1024


def _wide_sum(specs, leaves) -> bool:
    """Is any leaf a "sum" of 8-byte values? XLA:TPU scatters those as
    pairs of 32-bit values at 122-126 ns per update (6.8 ns for a 32-bit
    scatter on the same indices), so such a tree never scatters."""
    return any(s == "sum" and l.dtype.itemsize == 8
               for s, l in zip(specs, leaves))


def _wants_sorted_fold(specs, leaves, out_cap: int) -> bool:
    """Does the tree fold over sorted runs? Where it has a "sum" of
    8-byte values (:func:`_wide_sum`) and its static padded range
    ``out_cap`` has more than ``DENSE_FOLD_ROWS`` rows: the masked sums
    of the dense fold grow with the rows, the sort, the histogram and the
    gathers of the sorted fold do not. Decided from spec, dtype width
    and ``out_cap`` alone, so every backend runs the path the chip
    runs."""
    return out_cap > DENSE_FOLD_ROWS and _wide_sum(specs, leaves)


def _wants_dense_fold(specs, leaves, out_cap: int) -> bool:
    """The other side of :func:`_wants_sorted_fold`'s choice: a "sum" of
    8-byte values into at most ``DENSE_FOLD_ROWS`` rows. The whole tree
    then folds by masked reductions, its narrower leaves too."""
    return out_cap <= DENSE_FOLD_ROWS and _wide_sum(specs, leaves)


def _neutral_leaves(neutral, count: int) -> list:
    return (jax.tree.leaves(neutral) if neutral is not None
            else [None] * count)


def _zero_neutral(nv) -> bool:
    """A row no item reaches sums to zero: with this neutral it needs no
    presence mask."""
    return nv is None or not np.any(np.asarray(nv))


def _index_plan_kind(specs, leaves, neutral, out_cap: int):
    """Which index plan the tree's engine reads, if any: "sorted" for
    the fold over sorted runs; "dense" where a dense fold needs the
    first arrival of every row (a "first", "min" or "max" field, or a
    sum whose neutral is not zero, for the rows no item reaches); None
    where neither (the scatter, or a dense fold of zero-neutral sums)."""
    if _wants_sorted_fold(specs, leaves, out_cap):
        return "sorted"
    if _wants_dense_fold(specs, leaves, out_cap) and any(
            s != "sum" or not _zero_neutral(nv)
            for s, nv in zip(specs, _neutral_leaves(neutral, len(leaves)))):
        return "dense"
    return None


def _index_plan(kind: str, pos, out_cap: int):
    if kind == "sorted":
        return segmented.sorted_fold_plan(pos, out_cap)
    return segmented.dense_fold_plan(pos, out_cap)


@jax.named_scope(SCATTER_SCOPE)
def _scatter_reduce_apply(tree, pos, out_cap, specs, neutral, plan=None):
    """The dense ReduceToIndex phase without a sort of the items.

    With declarative FieldReduce specs a field's result is a direct
    ``.at[pos].add/min/max`` (deterministic: XLA applies duplicate
    updates in operand order) plus, for "first" fields, a scatter-min
    over arrival positions and one gather: 6.8 ns per update for values
    of 4 bytes or fewer on a v5e, which no sort of the rows beats. A
    "sum" of 8-byte values would cost 122-126 ns per update there
    (XLA:TPU's two-operand scatter), so a tree with such a leaf takes
    one of two folds, by ``out_cap`` (:func:`_wants_sorted_fold`):

    - into at most ``DENSE_FOLD_ROWS`` rows, the dense fold: every leaf
      is one masked sum, min or max per row in its own dtype, all rows
      in one pass over the leaf (core/segmented.py ``dense_fold``);
      "first" fields and presence masks read the first arrival of every
      row, a masked min (``dense_fold_plan``), computed only where one
      of them needs it;
    - into more, the fold over the runs of an index plan that sorts the
      32-bit target rows, never the items (``sorted_fold_sum``: one
      gather by the plan's permutation, 16 ns per row, a segmented scan
      and a gather at the run ends); "first" fields and presence masks
      read the same plan and the scatter-min is not run.

    Out-of-range indices are DROPPED (routed to the dump row) rather
    than clamped like the sorted engine's clip: they cannot occur
    through the public op (the exchange routes every item into its
    worker's range).

    ``pos``: target rows [cap] (:func:`_dense_pos`); ``out_cap``:
    static padded output rows; ``plan``: the index plan of ``pos``
    (:func:`_index_plan_kind`) where the caller has it (the fused
    segment's own), else it is computed here. Returns the dense output
    tree ([out_cap, ...] leaves, neutral at untouched rows).
    """
    leaves, td = jax.tree.flatten(tree)
    cap = pos.shape[0]
    dense = _wants_dense_fold(specs, leaves, out_cap)
    kind = _index_plan_kind(specs, leaves, neutral, out_cap)
    if kind is not None and plan is None:
        plan = _index_plan(kind, pos, out_cap)
    if kind == "dense":
        # a hoisted plan arrives as a plain tuple; its rows are ``pos``
        plan = segmented.DenseFoldPlan(*plan)
        pos = plan.pos
    win = None          # first-arrival winner per bin, computed lazily

    def winners():
        nonlocal win
        if win is None:
            arrival = jnp.where(pos < out_cap,
                                jnp.arange(cap, dtype=jnp.int32), cap)
            win = jnp.full(out_cap + 1, cap,
                           jnp.int32).at[pos].min(arrival)[:out_cap]
        return win

    def present_rows():
        if kind == "sorted":
            return plan[1][1:] > plan[1][:-1]
        if kind == "dense":
            return plan.first < cap
        return winners() < cap

    outs = []
    for s, leaf, nv in zip(specs, leaves,
                           _neutral_leaves(neutral, len(leaves))):
        trail = leaf.shape[1:]
        if s == "first":
            if kind == "sorted":
                col, present = segmented.sorted_fold_first(leaf, plan)
            elif kind == "dense":
                col, present = segmented.dense_fold_first(leaf, plan)
            else:
                w = winners()
                col = jnp.take(leaf, jnp.clip(w, 0, cap - 1), axis=0)
                present = w < cap
        elif s == "sum":
            from ...core import pallas_kernels as _pk
            if dense:
                col = segmented.dense_fold(leaf, pos, out_cap, "sum", 0)
            elif leaf.dtype.itemsize == 8:
                col = segmented.sorted_fold_sum(leaf, plan)
            elif (leaf.dtype == jnp.float32 and not trail
                    and _pk.pallas_enabled()
                    and _pk.segment_sum_ok(out_cap, cap)):
                # additive f32 fold through the Pallas segment-sum
                # kernel. Sum order differs from the scatter (per-block
                # partials), which the unordered-reduce contract
                # permits; the scatter below stays THE path whenever the
                # knob is off, so THRILL_TPU_PALLAS=0 is bit-identical
                # by construction.
                col = _pk.segment_sum_pallas(pos, leaf, out_cap)
            else:
                col = jnp.zeros((out_cap + 1,) + trail,
                                leaf.dtype).at[pos].add(leaf)[:out_cap]
            if _zero_neutral(nv):
                # zero neutral == what an untouched row sums to: skip
                # the presence mask (the PageRank/k-means hot shape)
                outs.append(col)
                continue
            present = present_rows()
        else:
            big = jnp.asarray(_type_max(np.dtype(leaf.dtype))
                              if s == "min"
                              else _type_min(np.dtype(leaf.dtype)),
                              leaf.dtype)
            if dense:
                col = segmented.dense_fold(leaf, pos, out_cap, s, big)
            else:
                base = jnp.full((out_cap + 1,) + trail, big, leaf.dtype)
                col = (base.at[pos].min(leaf) if s == "min"
                       else base.at[pos].max(leaf))[:out_cap]
            present = present_rows()
        fill = (jnp.zeros((), leaf.dtype) if nv is None
                else jnp.asarray(nv, leaf.dtype))
        pb = present.reshape(present.shape + (1,) * len(trail))
        outs.append(jnp.where(pb, col, fill))
    return jax.tree.unflatten(td, outs)


class ReduceToIndexNode(DIABase):
    """Key = dense index in [0, size); output is the dense array with
    ``neutral`` at unused indices (reference: api/reduce_to_index.hpp:60)."""

    def __init__(self, ctx, link, index_fn, reduce_fn, size, neutral) -> None:
        super().__init__(ctx, "ReduceToIndex", [link])
        self.index_fn = index_fn
        self.reduce_fn = reduce_fn
        self.size = int(size)
        self.neutral = neutral

    def _bounds(self):
        return dense_range_bounds(self.size, self.context.num_workers)

    def _exchange_by_index(self, shards, bounds, token):
        W = self.context.num_workers
        index_fn = self.index_fn
        bounds_dev = jnp.asarray(bounds)

        def dest(tree, mask, widx):
            idx = jnp.asarray(index_fn(tree)).astype(jnp.int64)
            return (jnp.searchsorted(bounds_dev[1:], idx, side="right")
                    ).astype(jnp.int32)

        return exchange.exchange(shards, dest, ("r2i_dest", token, W))

    def _fuse_segment(self, bounds: np.ndarray):
        """The dense scatter-reduce (post-exchange local phase) as a
        fused segment: sort by index, segmented-reduce, scatter into
        this worker's dense [range_size] rows."""
        from .. import fusion
        from ...common.config import round_up_pow2
        index_fn, reduce_fn = self.index_fn, self.reduce_fn
        neutral = self.neutral
        W = self.context.num_workers
        local_sizes = (bounds[1:] - bounds[:-1]).astype(np.int64)
        # pow2 cap like every other DeviceShards producer: a dense
        # result then has the SAME padded shape as a Generate'd table
        # of the same size, so loop carries (api/loop.py) are shape-
        # stable from iteration 0 and capture on the first pass
        out_cap = max(1, round_up_pow2(int(local_sizes.max())))
        ntok = None
        if neutral is not None:
            ntok = (str(jax.tree.structure(neutral)),
                    tuple(np.asarray(l).tobytes()
                          for l in jax.tree.leaves(neutral)))
        bound = (bounds[:W].astype(np.int64),
                 local_sizes.astype(np.int64))

        def local_index(tree, bound_t):
            starts, sizes = bound_t            # replicated [W] plans
            widx = lax.axis_index(AXIS)
            idx = jnp.asarray(index_fn(tree)).astype(jnp.int64)
            return idx, starts[widx], sizes[widx]

        def index_plan(fctx, tree, mask, bound_t):
            """The index plan of the fold over sorted runs or of a dense
            fold that needs first arrivals (:func:`_index_plan_kind`):
            it reads the index and the mask, no value, so a whole-loop
            program whose index is invariant runs it once, before the
            loop (api/fusion.py Segment.index_plan, api/loop.py)."""
            leaves, td = jax.tree.flatten(tree)
            sc = _scatter_fold_specs(reduce_fn, td, leaves)
            kind = (None if sc is None
                    else _index_plan_kind(sc, leaves, neutral, out_cap))
            if kind is None:
                return None
            idx, range_start, range_size = local_index(tree, bound_t)
            with jax.named_scope(SCATTER_SCOPE):
                return _index_plan(kind, _dense_pos(
                    mask, idx - range_start, range_size, out_cap), out_cap)

        def trace(fctx, tree, mask, bound_t):
            idx, range_start, range_size = local_index(tree, bound_t)
            leaves, td = jax.tree.flatten(tree)
            sc = _scatter_fold_specs(reduce_fn, td, leaves)
            if sc is not None:
                # declarative specs: no sort of the items
                out_tree = _scatter_reduce_apply(
                    tree, _dense_pos(mask, idx - range_start, range_size,
                                     out_cap),
                    out_cap, sc, neutral, plan=fctx.index_plan)
                return out_tree, jnp.arange(out_cap) < range_size
            specs = _device_fold_specs(reduce_fn, td, leaves)
            words = [idx.astype(jnp.uint64)]
            words, tree_s, valid, _ = segmented.sort_by_key_words(
                words, tree, mask)
            words, tree_s, n_runs = segmented.reduce_runs(
                words, tree_s, valid, reduce_fn, specs)
            pos = _run_pos(words[0], n_runs, range_start, out_cap)

            def scatter(leaf):
                base = jnp.zeros((out_cap + 1,) + leaf.shape[1:],
                                 leaf.dtype)
                return base.at[pos].set(leaf)[:out_cap]

            if neutral is None:
                out_tree = jax.tree.map(scatter, tree_s)
            else:
                def scatter_n(leaf, nval):
                    base = jnp.full((out_cap + 1,) + leaf.shape[1:],
                                    nval, leaf.dtype)
                    return base.at[pos].set(leaf)[:out_cap]
                out_tree = jax.tree.map(scatter_n, tree_s, neutral)
            return out_tree, jnp.arange(out_cap) < range_size

        return fusion.Segment(
            label="ReduceToIndex",
            token=("r2i_post_fused", (index_fn, reduce_fn, self.size),
                   out_cap, ntok),
            trace=trace, bound=bound, already_compact=True,
            sets_counts=local_sizes, dia_id=self.id,
            index_plan=index_plan)

    def compute_plan(self):
        from .. import fusion
        from ..functors import FieldReduce
        from ...core import host_radix
        plan = fusion.pull_plan(self.parents[0])
        bounds = self._bounds()
        # declarative FieldReduce specs take the fused segment on every
        # backend: its engines (the scatter, and the fold over sorted
        # runs for 8-byte sums) sort no items, need no device->host
        # demotion and no blocking column fetch, and stay in jax's async
        # dispatch stream, which loop replay (api/loop.py) rests on.
        # Everything else keeps the host-radix preference on the CPU
        # backend (XLA's single-core sort of the items is the wrong
        # engine there). Leaf dtypes are unknown until the plan
        # materializes, so this gate trusts the FieldReduce shape alone:
        # a spec the scatter engine later rejects (bool or non-numeric
        # sum/min/max leaf) still runs correctly through the fused
        # segment's sorted fallback, just on the slower engine
        if not plan.stitchable or (
                host_radix.eligible(self.context.mesh_exec)
                and not isinstance(self.reduce_fn, FieldReduce)):
            return fusion.wrap(self._compute_on(plan.finish(), bounds))
        W = self.context.num_workers
        token = (self.index_fn, self.reduce_fn, self.size)
        if W > 1:
            # exchange barrier: finish the upstream chain, shuffle,
            # start a fresh chain with the local scatter phase pending
            shards = self._exchange_by_index(plan.finish(), bounds,
                                             token)
            plan = fusion.FusionPlan(shards.mesh_exec, [shards])
        plan.append(self._fuse_segment(bounds))
        return plan

    def compute(self):
        plan = self.compute_plan()
        return plan.finish()

    def _compute_on(self, shards, bounds):
        """Pre-fusion compute body over pulled shards."""
        W = self.context.num_workers
        n = self.size
        if isinstance(shards, HostShards):
            return self._compute_host(shards, bounds)

        mex = shards.mesh_exec
        index_fn, reduce_fn = self.index_fn, self.reduce_fn
        token = (index_fn, reduce_fn, n)

        if W > 1:
            shards = self._exchange_by_index(shards, bounds, token)
            shards.validate_pending()    # optimistic-exchange heal point

        cap = shards.cap
        leaves, treedef = jax.tree.flatten(shards.tree)
        sc = _scatter_fold_specs(reduce_fn, treedef, leaves)
        if sc is None:
            # the sort-free scatter engine only takes declarative specs
            # over numeric leaves (and "first" anywhere); everything it
            # rejects — generic reduce functions AND FieldReduce specs
            # with unsupported leaf dtypes — still prefers the native
            # host engine on the CPU backend over XLA's single-core
            # sorted path
            host = _host_reduce_to_index(shards, index_fn, reduce_fn,
                                         bounds, self.neutral)
            if host is not None:
                return host

        # dense scatter-reduce into the local index range (pow2 cap —
        # shape-stable loop carries, see _fuse_segment)
        from ...common.config import round_up_pow2
        local_sizes = (bounds[1:] - bounds[:-1]).astype(np.int64)
        out_cap = max(1, round_up_pow2(int(local_sizes.max())))
        neutral = self.neutral
        specs = _device_fold_specs(reduce_fn, treedef, leaves)
        key = ("r2i_post", token, cap, out_cap, treedef,
               tuple((l.dtype, l.shape[2:]) for l in leaves))

        def build():
            def f(counts_dev, range_start, range_size, *ls):
                valid = jnp.arange(cap) < counts_dev[0, 0]
                tree = jax.tree.unflatten(treedef, [l[0] for l in ls])
                idx = jnp.asarray(index_fn(tree)).astype(jnp.int64)
                if sc is not None:
                    # the fused segment's engines, the same math:
                    # FUSE=0 runs produce identical results
                    out_tree = _scatter_reduce_apply(
                        tree, _dense_pos(valid, idx - range_start[0, 0],
                                         range_size[0, 0], out_cap),
                        out_cap, sc, neutral)
                    out_leaves = jax.tree.leaves(out_tree)
                    return (range_size[0].astype(jnp.int32)[None],
                            *[l[None] for l in out_leaves])
                words = [idx.astype(jnp.uint64)]
                words, tree, valid, _ = segmented.sort_by_key_words(
                    words, tree, valid)
                words, tree, n_runs = segmented.reduce_runs(
                    words, tree, valid, reduce_fn, specs)
                pos = _run_pos(words[0], n_runs, range_start[0, 0],
                               out_cap)

                def scatter(leaf):
                    base = jnp.zeros((out_cap + 1,) + leaf.shape[1:],
                                     leaf.dtype)
                    return base.at[pos].set(leaf)[:out_cap]

                if neutral is None:
                    out_tree = jax.tree.map(scatter, tree)
                else:
                    def scatter_n(leaf, nval):
                        base = jnp.full((out_cap + 1,) + leaf.shape[1:],
                                        nval, leaf.dtype)
                        return base.at[pos].set(leaf)[:out_cap]
                    out_tree = jax.tree.map(scatter_n, tree, neutral)
                out_leaves = jax.tree.leaves(out_tree)
                return (range_size[0].astype(jnp.int32)[None],
                        *[l[None] for l in out_leaves])

            return mex.smap(f, 3 + len(leaves))

        fn = mex.cached(key, build)
        kind = (None if sc is None
                else _index_plan_kind(sc, leaves, neutral, out_cap))
        if kind is not None:
            # the plan in place: every dispatch of ``fn`` counts it
            from .. import fusion
            fusion.note_index_plans(fn, fusion.IndexPlans(
                count=1, dense=int(kind == "dense")))
        rs = mex.put_small(bounds[:W].astype(np.int64)[:, None])
        rsz = mex.put_small(local_sizes[:, None])
        out = fn(shards.counts_device(), rs, rsz, *leaves)
        tree = jax.tree.unflatten(treedef, list(out[1:]))
        return DeviceShards(mex, tree, local_sizes)

    def _compute_host(self, shards: HostShards, bounds):
        W = shards.num_workers
        index_fn, reduce_fn = self.index_fn, self.reduce_fn
        tables = [dict() for _ in range(W)]
        for items in shards.lists:
            for it in items:
                i = int(index_fn(it))
                w = int(np.searchsorted(bounds[1:], i, side="right"))
                t = tables[w]
                t[i] = reduce_fn(t[i], it) if i in t else it
        out = []
        for w in range(W):
            lo, hi = int(bounds[w]), int(bounds[w + 1])
            out.append([tables[w].get(i, self.neutral)
                        for i in range(lo, hi)])
        return HostShards(W, out)


def ReduceToIndex(dia: DIA, index_fn, reduce_fn, size, neutral=None) -> DIA:
    return DIA(ReduceToIndexNode(dia.context, dia._link(), index_fn,
                                 reduce_fn, size, neutral))
