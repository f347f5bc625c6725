"""InnerJoin.

Reference: thrill/api/inner_join.hpp:61 — hash-partition both sides,
local merge-join after sorting spilled files (optional LocationDetection
to skip shipping unmatched keys).

Device path: both sides exchange by the same key hash, then one jitted
local sort-merge-join per worker: sort left and right by key words,
count per-right-item match runs, a host capacity agreement sizes the
pair expansion, and a second jitted program gathers the (left, right)
pairs and applies ``join_fn`` batched. The expansion indices come from
searchsorted over the pair-offset cumsum — branch-free, static shapes.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ...common import hashing
from ...common.partition import dense_range_bounds
from ...parallel.mesh import AXIS
from ...core import keys as keymod
from ...core import segmented
from ...data import exchange
from ...data.shards import DeviceShards, HostShards
from ...common.config import round_up_pow2
from ..dia import DIA
from ..dia_base import DIABase

# the name the dense-index join's gather carries in a device profile
# (jax.named_scope: HLO metadata, no operation added), beside
# core/rowmove.py's and core/device_sort.py's
DENSE_SCOPE = "join_gather"


class InnerJoinNode(DIABase):
    def __init__(self, ctx, llink, rlink, lkey, rkey, join_fn,
                 location_detection=None,
                 out_size_hint=None, dense_right_index=None) -> None:
        super().__init__(ctx, "InnerJoin", [llink, rlink])
        if dense_right_index is not None and rkey is not None:
            # the dense contract DEFINES the right key as the row's
            # global position; a caller-supplied right key would be
            # honored by the host path but ignored by the device
            # gather — storage-dependent results, so refuse it
            raise ValueError(
                "InnerJoin: dense_right_index defines the right key as "
                "the row's dense position; right_key_fn must be None")
        self.lkey = lkey
        self.rkey = rkey
        self.join_fn = join_fn
        # DENSE INDEX JOIN contract: the right side is a dense table of
        # exactly ``dense_right_index`` rows whose key at global
        # position g is g (a ZipWithIndex over a ReduceToIndex/Generate
        # table — the PageRank rank/degree tables). The join is then a
        # pure GATHER: no sort, no hash, no exchange — the device
        # program all_gathers the (small) right table and indexes it by
        # the left keys. O(n) like the numpy proxy's fancy-indexing,
        # where the generic sort-merge join pays two XLA argsorts per
        # call (~43 ms each at 64 k rows on XLA:CPU). Out-of-range left
        # keys simply produce no pair (inner-join semantics); there is
        # no overflow to detect, so no deferred check and no size sync
        # at ANY worker count.
        self.dense_right_index = (None if dense_right_index is None
                                  else int(dense_right_index))
        # reference: LocationDetectionTag, api/inner_join.hpp:161-190 —
        # prune items whose key hash exists on only one side before the
        # shuffle. None (the default) = decided by the plan-time cost
        # model (core/preshuffle.py: estimated fingerprint bytes vs
        # estimated pruned row bytes, fed by the learned per-site
        # exchange capacities); True/False force it like the
        # reference's explicit tag
        self.location_detection = location_detection
        # PER-WORKER output capacity hint: when the caller knows an
        # upper bound on each worker's match count (index joins with
        # known multiplicity — PageRank's edges-by-src join emits
        # exactly one pair per edge), the device path skips its
        # blocking device->host size sync and keeps the whole join in
        # jax's async-dispatch stream.
        # Overflow is detected before any consumer reads the columns
        # and recovers by re-running the expansion un-hinted (or raises
        # with THRILL_TPU_JOIN_RECOVER=0 — never silently truncates).
        # TPU-native extension:
        # the reference sizes from its spilled files host-side
        # (api/inner_join.hpp:208) and has no such sync to skip.
        self.out_size_hint = out_size_hint

    def compute(self):
        left = self.parents[0].pull()
        right = self.parents[1].pull()
        if isinstance(left, HostShards) or isinstance(right, HostShards):
            return self._compute_host(left, right)
        return self._compute_device(left, right)

    # -- host path ------------------------------------------------------
    def _compute_host(self, left, right):
        if isinstance(left, DeviceShards):
            left = left.to_host_shards("join-host-path")
        if isinstance(right, DeviceShards):
            right = right.to_host_shards("join-host-path")
        W = left.num_workers
        mex = self.context.mesh_exec
        from ...data import multiplexer
        lkey, rkey, jfn = self.lkey, self.rkey, self.join_fn
        if self.dense_right_index is not None and rkey is None:
            # dense-index contract on the host path: the right key IS
            # the row's global position in the dense table (the device
            # gather's addressing), so enumerate and join on that.
            # Worker w's first row sits at dense_range_bounds[w] BY THE
            # CONTRACT — never at the cumulative length of the
            # preceding lists, which is wrong multi-controller (the
            # host-storage invariant keeps non-local workers' lists
            # empty, so cumulative offsets would collapse toward 0)
            bounds = dense_range_bounds(self.dense_right_index,
                                        W).tolist()
            enum_lists = []
            for w, items in enumerate(right.lists):
                enum_lists.append([(bounds[w] + i, it)
                                   for i, it in enumerate(items)])
            right = HostShards(W, enum_lists)
            inner = jfn
            rkey = _enum_key
            jfn = lambda l, r: inner(l, r[1])  # noqa: E731
        # hash each item once; reuse for detection, pruning and shuffle
        lh = [[hashing.stable_host_hash(_h(lkey(it))) for it in l]
              for l in left.lists]
        rh = [[hashing.stable_host_hash(_h(rkey(it))) for it in l]
              for l in right.lists]
        ld = self.location_detection
        if ld is None:
            # host path: exact local row counts feed the cost model
            # (local_rows: multi-controller runs all-reduce them to
            # the global count before deciding, core/preshuffle.py)
            from ...core import preshuffle
            rows = (sum(len(l) for l in left.lists)
                    + sum(len(l) for l in right.lists))
            ld = preshuffle.auto_location_detect(
                mex, rows, 32, ("join_host", self.lkey, self.rkey),
                local_rows=True)
        if ld and W > 1:
            from ...core.location_detection import (LocationDetection,
                                                    _MASK)
            lh_all, rh_all = lh, rh
            if multiplexer.multiprocess(mex):
                # exchange the FINGERPRINTS (not the items) so every
                # controller agrees on the common-hash set (reference:
                # core/location_detection.hpp:70 ships Golomb-coded
                # hashes the same way)
                def _gather(hs):
                    local = {w: hs[w] for w in mex.local_workers}
                    out = [[] for _ in range(W)]
                    for msg in mex.host_net.all_gather(local):
                        for w, v in msg.items():
                            out[int(w)] = v
                    return out
                lh_all, rh_all = _gather(lh), _gather(rh)
            ld_l = LocationDetection(W)
            ld_r = LocationDetection(W)
            for w in range(W):
                ld_l.add_worker(w, lh_all[w])
                ld_r.add_worker(w, rh_all[w])
            common = ld_l.common_hashes(ld_r)

            def prune(shards, hs):
                kept_items, kept_hashes = [], []
                for items, hlist in zip(shards.lists, hs):
                    ki, kh = [], []
                    for it, h in zip(items, hlist):
                        if h & _MASK in common:
                            ki.append(it)
                            kh.append(h)
                    kept_items.append(ki)
                    kept_hashes.append(kh)
                return HostShards(W, kept_items), kept_hashes

            left, lh = prune(left, lh)
            right, rh = prune(right, rh)

        def shuffle(shards, hs):
            # items travel tagged with their precomputed hash (computed
            # once at line 62, survives pruning in lock-step)
            tagged = HostShards(W, [[(h, it) for it, h in zip(items, hl)]
                                    for items, hl in zip(shards.lists, hs)])
            # hash-partition target (MixStream-eligible): the join
            # matches by key, so batch arrival order only permutes the
            # output row order under THRILL_TPU_HOST_MIX=1
            ex = multiplexer.host_exchange(mex, tagged,
                                           lambda p: p[0] % W,
                                           reason="join",
                                           rank_order=False)
            return HostShards(W, [[it for _, it in l] for l in ex.lists])

        lx = shuffle(left, lh)
        rx = shuffle(right, rh)
        out = []
        for litems, ritems in zip(lx.lists, rx.lists):
            table = {}
            for it in litems:
                table.setdefault(_h(lkey(it)), []).append(it)
            pairs = []
            for rt in ritems:
                for lt in table.get(_h(rkey(rt)), ()):
                    pairs.append(jfn(lt, rt))
            out.append(pairs)
        return HostShards(W, out)

    # -- device path ----------------------------------------------------
    def _prep_device(self, left: DeviceShards, right: DeviceShards,
                     token):
        """Location filter + hash-partition exchange (fusion barriers
        shared by the phased and the stitched join paths)."""
        mex = left.mesh_exec
        W = mex.num_workers
        lkey, rkey = self.lkey, self.rkey

        ld = self.location_detection
        if ld is None and W > 1:
            # plan-time cost model: fingerprint register bytes vs the
            # rows pruning is expected to save, fed by exact counts
            # where host-known and the learned per-site exchange
            # capacities otherwise (core/preshuffle.py)
            from ...core import preshuffle
            rows, item_bytes = preshuffle.join_rows_estimate(
                mex, left, right, ("join_l", token, W),
                ("join_r", token, W))
            ld = preshuffle.auto_location_detect(mex, rows, item_bytes,
                                                 ("join_dev", token))
        if ld and W > 1:
            pre_rows = _host_rows(left), _host_rows(right)
            left, right = _location_filter(left, right, lkey, rkey,
                                           token)
        else:
            pre_rows = None

        if W > 1:
            def mk_dest(key_fn):
                def dest(tree, mask, widx):
                    words = keymod.encode_key_words(key_fn(tree))
                    h = hashing.hash_key_words(words)
                    return (h % jnp.uint64(W)).astype(jnp.int32)
                return dest

            left = exchange.exchange(left, mk_dest(lkey),
                                     ("join_l", token, W))
            right = exchange.exchange(right, mk_dest(rkey),
                                      ("join_r", token, W))
            # optimistic (capacity-cached) exchanges owe a deferred
            # overflow check; the join phases read the columns directly
            left.validate_pending()
            right.validate_pending()
            if pre_rows is not None:
                # teach the site its prune fraction where both counts
                # happen to be host-known already (never adds a sync)
                post = _host_rows(left), _host_rows(right)
                if None not in pre_rows and None not in post:
                    from ...core import preshuffle
                    preshuffle.record_prune(
                        mex, ("join_dev", token),
                        pre_rows[0] + pre_rows[1], post[0] + post[1])
        return left, right

    def compute_plan(self):
        """Hinted joins stitch (api/fusion.py): both phases trace into
        ONE program, and the plan defers so downstream device ops ride
        in the same dispatch. Un-hinted joins need their host size
        agreement — a fusion barrier — and stay on the phased path.
        Dense-index joins stitch unconditionally (gather, no sync)."""
        from .. import fusion
        if not fusion.enabled() or (self.out_size_hint is None
                                    and self.dense_right_index is None):
            return None
        left = self.parents[0].pull()
        right = self.parents[1].pull()
        if isinstance(left, HostShards) or isinstance(right, HostShards):
            return fusion.wrap(self._compute_host(left, right))
        token = (self.lkey, self.rkey, self.join_fn)
        if self.dense_right_index is not None:
            self._check_dense(right)
            return fusion.FusionPlan(
                left.mesh_exec, [left, right],
                head=self._dense_head(right.cap, token))
        left, right = self._prep_device(left, right, token)
        return self._fused_plan(left, right, token)

    # -- dense-index join ----------------------------------------------
    def _dense_bounds(self) -> np.ndarray:
        return dense_range_bounds(self.dense_right_index,
                                  self.context.num_workers)

    def _check_dense(self, right: DeviceShards) -> None:
        """Validate the dense contract where it is free: host-known
        right counts must match the dense range split (ReduceToIndex /
        Generate layouts). Device-resident counts are trusted — forcing
        a sync here would defeat the point of the gather join."""
        counts = right._counts_host
        if counts is None:
            return
        expect = np.diff(self._dense_bounds())
        if not np.array_equal(np.asarray(counts), expect):
            raise ValueError(
                f"InnerJoin dense_right_index={self.dense_right_index}: "
                f"right side counts {np.asarray(counts).tolist()} do not "
                f"form the dense range split {expect.tolist()}")

    def _dense_head(self, rcap: int, token):
        from .. import fusion
        n = self.dense_right_index
        W = self.context.num_workers
        bounds = self._dense_bounds()
        lkey, jfn = self.lkey, self.join_fn

        def trace(fctx, states, _bound):
            (ltree, lmask), (rtree, _rmask) = states
            key = jnp.asarray(lkey(ltree)).astype(jnp.int64)
            if W == 1:
                rall = rtree
                gidx = jnp.clip(key, 0, rcap - 1)
            else:
                b = jnp.asarray(bounds)
                w = jnp.clip(jnp.searchsorted(b[1:], key, side="right"),
                             0, W - 1)
                gidx = jnp.clip(w * rcap + (key - b[w]),
                                0, W * rcap - 1)
                rall = jax.tree.map(
                    lambda x: lax.all_gather(x, AXIS).reshape(
                        (W * rcap,) + x.shape[1:]), rtree)
            with jax.named_scope(DENSE_SCOPE):
                rsel = jax.tree.map(lambda x: jnp.take(x, gidx, axis=0),
                                    rall)
            out = jfn(ltree, rsel)
            return out, lmask & (key >= 0) & (key < n)

        return fusion.Segment(label="InnerJoin",
                              token=("join_dense", token, n),
                              trace=trace, dia_id=self.id)

    def _compute_dense(self, left: DeviceShards,
                       right: DeviceShards) -> DeviceShards:
        """Unfused twin of the dense-index gather join (THRILL_TPU_FUSE=0
        parity path): one program, same gather math, compacted output."""
        from ...data.shards import compact_valid
        mex = left.mesh_exec
        self._check_dense(right)
        head = self._dense_head(right.cap,
                                (self.lkey, self.rkey, self.join_fn))
        lcap, rcap = left.cap, right.cap
        lleaves, ltd = jax.tree.flatten(left.tree)
        rleaves, rtd = jax.tree.flatten(right.tree)
        nl = len(lleaves)
        key = ("join_dense_solo", (self.lkey, self.rkey, self.join_fn),
               self.dense_right_index, lcap, rcap, ltd, rtd,
               tuple((l.dtype, l.shape[2:]) for l in lleaves),
               tuple((l.dtype, l.shape[2:]) for l in rleaves))
        holder = {}

        def build():
            def f(lc, rc, *ls):
                ltree = jax.tree.unflatten(ltd, [x[0] for x in ls[:nl]])
                rtree = jax.tree.unflatten(rtd, [x[0] for x in ls[nl:]])
                lmask = jnp.arange(lcap) < lc[0, 0]
                rmask = jnp.arange(rcap) < rc[0, 0]
                tree, mask = head.trace(None, [(ltree, lmask),
                                               (rtree, rmask)], None)
                tree, count = compact_valid(tree, mask)
                out_leaves, out_td = jax.tree.flatten(tree)
                holder["treedef"] = out_td
                return (count[None, None].astype(jnp.int32),
                        *[x[None] for x in out_leaves])

            return mex.smap(f, 2 + nl + len(rleaves)), holder

        fn, h = mex.cached(key, build)
        out = fn(left.counts_device(), right.counts_device(),
                 *lleaves, *rleaves)
        tree = jax.tree.unflatten(h["treedef"], list(out[1:]))
        return DeviceShards(mex, tree, out[0])

    def _fused_plan(self, left: DeviceShards, right: DeviceShards,
                    token):
        """One-dispatch hinted join: sort both sides, count match runs,
        expand pairs — phase 1 + phase 2 of the phased path as a single
        head segment. The true per-worker totals ride out as an aux
        output feeding the deferred overflow check; recovery
        re-dispatches the plan (sources are immutable device buffers —
        the lineage) at the true capacity."""
        from .. import fusion
        mex = left.mesh_exec
        lkey, rkey, jfn = self.lkey, self.rkey, self.join_fn
        out_cap = round_up_pow2(max(int(self.out_size_hint), 1))
        node = self

        def make_head(cap_):
            def trace(fctx, states, _bound):
                (ltree, lmask), (rtree, rmask) = states
                lcap = lmask.shape[0]
                rcap = rmask.shape[0]
                lw = keymod.encode_key_words(lkey(ltree))
                rw = keymod.encode_key_words(rkey(rtree))
                lw, ltree_s, lvalid, _ = segmented.sort_by_key_words(
                    lw, ltree, lmask)
                rw, rtree_s, rvalid, _ = segmented.sort_by_key_words(
                    rw, rtree, rmask)
                lo, hi = _run_bounds(lw, lvalid, rw, rvalid)
                matches = jnp.where(rvalid, hi - lo, 0)      # [rcap]
                total = jnp.sum(matches)
                fctx.emit_aux("join_totals", total)
                ends = jnp.cumsum(matches)
                p = jnp.arange(cap_, dtype=jnp.int64)
                ridx = jnp.searchsorted(ends, p, side="right")
                ridx = jnp.clip(ridx, 0, rcap - 1)
                starts = ends - matches
                lidx = lo[ridx] + (p - starts[ridx])
                lidx = jnp.clip(lidx, 0, lcap - 1)
                lsel = jax.tree.map(
                    lambda x: jnp.take(x, lidx, axis=0), ltree_s)
                rsel = jax.tree.map(
                    lambda x: jnp.take(x, ridx, axis=0), rtree_s)
                return jfn(lsel, rsel), jnp.arange(cap_) < total

            def finalize(plan, out):
                node._attach_fused_check(mex, plan, out, cap_)

            return fusion.Segment(label="InnerJoin",
                                  token=("join_fused", token, cap_),
                                  trace=trace, already_compact=True,
                                  refit=make_head, finalize=finalize,
                                  dia_id=node.id)

        return fusion.FusionPlan(mex, [left, right],
                                 head=make_head(out_cap))

    def _attach_fused_check(self, mex, plan, out: DeviceShards,
                            cap: int) -> None:
        """PR-1 recovery semantics for the stitched join: deferred
        overflow check draining at the fused boundary, sticky error
        state, in-place heal by re-dispatching the plan at the true
        capacity (counts replaced too — a fused tail's output counts
        depend on the healed pairs).

        TWIN of the phased path's check in ``_compute_device`` below
        (same sticky/resolve/re-entrancy discipline, different heal:
        plan re-dispatch vs expand-closure re-run) — a change to
        either must be mirrored in the other."""
        totals_dev = plan.aux.get("join_totals")
        try:
            totals_dev.copy_to_host_async()
        except Exception:
            pass                   # overlap is best-effort, not needed
        hint = self.out_size_hint
        label, dia_id = self.label, self.id
        hbm = self.context.hbm
        state = {"ok": False, "err": None, "plan": plan, "out": out,
                 "totals": totals_dev}

        def _resolve() -> None:
            state["ok"] = state["err"] is None
            state["plan"] = None
            state["out"] = None
            state["totals"] = None

        def validate(_counts):
            if state["err"] is not None:
                raise state["err"]
            if state["ok"]:
                return None
            totals = mex._fetch_raw(
                state["totals"]).reshape(-1).astype(np.int64)
            if int(totals.max(initial=0)) <= cap:
                _resolve()
                return None
            worst = int(totals.max(initial=0))
            import os
            if os.environ.get("THRILL_TPU_JOIN_RECOVER", "1") != "0":
                true_cap = round_up_pow2(max(worst, 1))
                o, plan_ = state["out"], state["plan"]
                # resolve FIRST: the re-dispatch below realizes counts,
                # and a drain fired from inside it must see a resolved
                # check, never start a second recovery
                _resolve()
                healed = plan_.reexecute(true_cap)
                o.tree = healed.tree
                o._counts_dev = healed._counts_dev
                # _fetch_raw: no drain (re-entrancy) and no counted
                # mid-pipeline sync in the dispatch budget
                new_counts = mex._fetch_raw(
                    healed._counts_dev).reshape(-1).astype(np.int64)
                mex.stats_join_overflow_retries += 1
                # resync the governor if some node tracks these shards
                # (the consumer of a deferred chain cached them)
                for n in list(hbm._lru.values()):
                    if n._shards is o and getattr(n, "_hbm_bytes", 0):
                        nb = hbm._device_bytes(o)
                        hbm.mem.subtract(n._hbm_bytes)
                        n._hbm_bytes = nb
                        hbm.mem.add(nb)
                        break
                from ...common import faults
                faults.note("recovery", what="join_out_size_hint",
                            node=label, dia_id=dia_id, hint=int(hint),
                            true_max=worst, new_cap=true_cap,
                            fused=True)
                return new_counts
            state["err"] = ValueError(
                f"InnerJoin out_size_hint={hint} (cap {cap}) "
                f"overflowed: a worker produced {worst} pairs; "
                f"results were truncated — raise the hint or drop it")
            _resolve()
            raise state["err"]

        out._counts_check = validate

        def pending_check() -> None:
            if state["err"] is not None:
                raise state["err"]       # sticky: a drain surfaces it
            if state["ok"]:
                return
            validate(None)

        mex._pending_checks.append(pending_check)

    def _compute_device(self, left: DeviceShards, right: DeviceShards):
        mex = left.mesh_exec
        W = mex.num_workers
        lkey, rkey, jfn = self.lkey, self.rkey, self.join_fn
        token = (lkey, rkey, jfn)

        if self.dense_right_index is not None:
            # gather join: no partition exchange, no size agreement
            return self._compute_dense(left, right)

        left, right = self._prep_device(left, right, token)

        if self.out_size_hint is not None:
            from .. import fusion
            if fusion.enabled():
                return self._fused_plan(left, right, token).execute()

        lcap, rcap = left.cap, right.cap
        lleaves, ltd = jax.tree.flatten(left.tree)
        rleaves, rtd = jax.tree.flatten(right.tree)

        # phase 1: sort both sides, count pairs per right item
        key1 = ("join_count", token, lcap, rcap, ltd, rtd,
                tuple((l.dtype, l.shape[2:]) for l in lleaves),
                tuple((l.dtype, l.shape[2:]) for l in rleaves))
        nl = len(lleaves)

        def build1():
            def f(lc, rc, *ls):
                ltree = jax.tree.unflatten(ltd, [x[0] for x in ls[:nl]])
                rtree = jax.tree.unflatten(rtd, [x[0] for x in ls[nl:]])
                lvalid = jnp.arange(lcap) < lc[0, 0]
                rvalid = jnp.arange(rcap) < rc[0, 0]
                lw = keymod.encode_key_words(lkey(ltree))
                rw = keymod.encode_key_words(rkey(rtree))
                lw, ltree_s, lvalid, _ = segmented.sort_by_key_words(
                    lw, ltree, lvalid)
                rw, rtree_s, rvalid, _ = segmented.sort_by_key_words(
                    rw, rtree, rvalid)
                lo, hi = _run_bounds(lw, lvalid, rw, rvalid)
                matches = jnp.where(rvalid, hi - lo, 0)  # [rcap]
                total = jnp.sum(matches)
                return (total[None, None].astype(jnp.int64),
                        matches[None], lo[None],
                        *[x[None] for x in jax.tree.leaves(ltree_s)],
                        *[x[None] for x in jax.tree.leaves(rtree_s)])

            return mex.smap(f, 2 + nl + len(rleaves))

        f1 = mex.cached(key1, build1)
        out1 = f1(left.counts_device(), right.counts_device(),
                  *lleaves, *rleaves)
        matches_dev, lo_dev = out1[1], out1[2]
        lsorted = list(out1[3:3 + nl])
        rsorted = list(out1[3 + nl:])

        totals = None
        if self.out_size_hint is not None:
            out_cap = round_up_pow2(max(int(self.out_size_hint), 1))
        else:
            totals = mex.fetch(out1[0]).reshape(-1).astype(np.int64)
            out_cap = round_up_pow2(max(int(totals.max()), 1))

        # phase 2: expand pairs and apply join_fn. ``expand`` is the
        # re-runnable half of the join's lineage: phase-1 outputs
        # (sorted sides + per-item match runs) plus a capacity fully
        # determine the result, so the overflow recovery below can
        # re-execute it at the TRUE capacity without touching parents.
        def expand(cap_: int):
            key2 = ("join_expand", token, lcap, rcap, cap_, ltd, rtd,
                    tuple((l.dtype, l.shape[2:]) for l in lleaves),
                    tuple((l.dtype, l.shape[2:]) for l in rleaves))
            holder = {}

            def build2():
                def f(matches, lo, *ls):
                    m = matches[0]                   # [rcap] pair counts
                    lo_ = lo[0]                      # [rcap] left run start
                    ltree = jax.tree.unflatten(ltd,
                                               [x[0] for x in ls[:nl]])
                    rtree = jax.tree.unflatten(rtd,
                                               [x[0] for x in ls[nl:]])
                    ends = jnp.cumsum(m)             # [rcap]
                    p = jnp.arange(cap_, dtype=jnp.int64)
                    ridx = jnp.searchsorted(ends, p, side="right")
                    ridx = jnp.clip(ridx, 0, rcap - 1)
                    starts = ends - m
                    lidx = lo_[ridx] + (p - starts[ridx])
                    lidx = jnp.clip(lidx, 0, lcap - 1)
                    lsel = jax.tree.map(
                        lambda x: jnp.take(x, lidx, axis=0), ltree)
                    rsel = jax.tree.map(
                        lambda x: jnp.take(x, ridx, axis=0), rtree)
                    out = jfn(lsel, rsel)
                    out_leaves, out_td = jax.tree.flatten(out)
                    holder["treedef"] = out_td
                    return tuple(x[None] for x in out_leaves)

                # (fn, holder) pair is what gets cached: a cache HIT
                # must read the FIRST build's holder (filled at trace
                # time) — a fresh local dict would be empty (the Merge
                # regression, test_merge_executable_cache_hit)
                return mex.smap(f, 2 + nl + len(rleaves)), holder

            f2, h2 = mex.cached(key2, build2)
            out2 = f2(matches_dev, lo_dev, *lsorted, *rsorted)
            return jax.tree.unflatten(h2["treedef"], list(out2))

        tree = expand(out_cap)
        if totals is not None:
            return DeviceShards(mex, tree, totals)
        # hint path: counts stay on device (no host sync; the eager
        # astype is one more async device op in the stream). Kick the
        # totals' device->host copy off NOW so the deferred validation
        # at the consumer's pull confirms an already-landed value
        # instead of stalling the dispatch stream.
        out = DeviceShards(mex, tree, out1[0].astype(jnp.int32))
        cap, hint, totals_dev = out_cap, self.out_size_hint, out1[0]
        try:
            totals_dev.copy_to_host_async()
        except Exception:
            pass                   # overlap is best-effort, not needed
        # state is STICKY on failure: once an overflow is detected with
        # recovery disabled, every later validation re-raises — a
        # caller that swallows the first error (bench metric wrappers
        # catch Exception) can never silently read truncated data.
        # COST, accepted deliberately: until the first consumer
        # validates (normally the very next pull), the ``expand``
        # closure pins the phase-1 outputs (sorted copies of both
        # sides + match runs, ~the join's input size) in HBM as the
        # recovery lineage, and that validation blocks the host on
        # phase-1 completion (overlapped with phase-2's already-
        # dispatched execution; the D2H copy itself was started async
        # above). ALL device refs live in ``state`` and are nulled the
        # moment the check resolves, so the entry that may linger in
        # mex._pending_checks until the next drain pins nothing — a
        # spilled node's HBM really frees.
        state = {"ok": False, "err": None, "expand": expand,
                 "out": out, "totals": totals_dev}
        label, dia_id = self.label, self.id
        node, hbm = self, self.context.hbm

        def _resolve() -> None:
            state["ok"] = state["err"] is None
            state["expand"] = None
            state["out"] = None
            state["totals"] = None

        def validate(counts: np.ndarray) -> None:
            if state["err"] is not None:
                raise state["err"]
            if state["ok"]:
                return
            worst = int(counts.max(initial=0))
            if worst > cap:
                import os
                if os.environ.get("THRILL_TPU_JOIN_RECOVER",
                                  "1") != "0":
                    # lineage retry: re-run the expansion at the true
                    # capacity and heal the shards IN PLACE — every
                    # consumer validates before reading the columns
                    # (ParentLink.pull / counts / egress drains), so
                    # the truncated tree was never observable
                    true_cap = round_up_pow2(max(worst, 1))
                    o = state["out"]
                    o.tree = state["expand"](true_cap)
                    mex.stats_join_overflow_retries += 1
                    if (node._shards is o
                            and getattr(node, "_hbm_bytes", 0)):
                        # the healed tree is larger than what on_cache
                        # accounted: resync the governor or the budget
                        # drifts under-counted forever. ACCOUNTING
                        # ONLY — no maybe_spill from in here:
                        # validation runs inside arbitrary frames
                        # (another node's spill, a parent pull
                        # mid-materialize), and evicting from this
                        # depth can re-enter an unresolved sibling's
                        # recovery or spill shards an ancestor frame
                        # is actively returning. The next natural
                        # pressure event (on_cache/touch) evicts.
                        nb = hbm._device_bytes(o)
                        hbm.mem.subtract(node._hbm_bytes)
                        node._hbm_bytes = nb
                        hbm.mem.add(nb)
                    # resolve before the note so a re-entrant
                    # validation is a no-op, never a second recovery
                    _resolve()
                    # ONE emission: note() counts the recovery and
                    # forwards to the Context's JSON logger (attached
                    # in Context.__init__)
                    from ...common import faults
                    faults.note("recovery", what="join_out_size_hint",
                                node=label, dia_id=dia_id,
                                hint=int(hint), true_max=worst,
                                new_cap=true_cap)
                    return
                state["err"] = ValueError(
                    f"InnerJoin out_size_hint={hint} (cap {cap}) "
                    f"overflowed: a worker produced {worst} pairs; "
                    f"results were truncated — raise the hint or "
                    f"drop it")
                _resolve()
                raise state["err"]
            _resolve()

        out._counts_check = validate

        def pending_check() -> None:
            # fetch drains catch chains that never realize THIS
            # shards' counts. Skip the totals transfer once resolved;
            # the transfer uses _fetch_raw (multi-controller safe, no
            # stats, and the drain already swapped the queue out so
            # re-entrancy cannot loop)
            if state["err"] is not None:
                raise state["err"]      # sticky: a drain surfaces it
            if state["ok"]:
                return
            validate(mex._fetch_raw(state["totals"]).reshape(-1))

        mex._pending_checks.append(pending_check)
        return out


def _host_rows(shards) -> "int | None":
    """Global row count when already host-known (no sync), else None."""
    counts = getattr(shards, "_counts_host", None)
    return None if counts is None else int(np.asarray(counts).sum())


def _location_filter(left: DeviceShards, right: DeviceShards,
                     lkey, rkey, token):
    """Device LocationDetection: drop items whose key hash has no
    presence on the OTHER side anywhere in the cluster, before paying
    for the exchange (reference: LocationDetectionTag,
    api/inner_join.hpp:161-190, core/location_detection.hpp:70 — the
    Golomb-coded per-key location exchange becomes one pmax over
    presence registers). Registers are u8 presence bits sized to the
    padded row bound (core/preshuffle.py register_width) — false
    positives only cost shuffle traffic, never correctness."""
    import jax
    from jax import lax

    from ...core import preshuffle
    from ...data.shards import compact_valid
    from ...parallel.mesh import AXIS

    mex = left.mesh_exec
    lcap, rcap = left.cap, right.cap
    M = preshuffle.register_width((lcap + rcap) * mex.num_workers)
    lleaves, ltd = jax.tree.flatten(left.tree)
    rleaves, rtd = jax.tree.flatten(right.tree)
    nl = len(lleaves)
    key = ("join_ld", token, M, lcap, rcap, ltd, rtd,
           tuple((l.dtype, l.shape[2:]) for l in lleaves),
           tuple((l.dtype, l.shape[2:]) for l in rleaves))

    def build():
        def f(lc, rc, *ls):
            ltree = jax.tree.unflatten(ltd, [x[0] for x in ls[:nl]])
            rtree = jax.tree.unflatten(rtd, [x[0] for x in ls[nl:]])
            lvalid = jnp.arange(lcap) < lc[0, 0]
            rvalid = jnp.arange(rcap) < rc[0, 0]
            hl = (hashing.hash_key_words(
                keymod.encode_key_words(lkey(ltree)))
                % jnp.uint64(M)).astype(jnp.int32)
            hr = (hashing.hash_key_words(
                keymod.encode_key_words(rkey(rtree)))
                % jnp.uint64(M)).astype(jnp.int32)
            # u8 presence registers: a quarter of the i32 form's
            # fabric bytes, same verdict. Filled by the Pallas
            # presence kernel where it engages (bit-identical —
            # presence is 0/1, no float reassociation).
            from ...core.pallas_kernels import presence_fill
            pres_l = presence_fill(hl, lvalid, M)
            pres_r = presence_fill(hr, rvalid, M)
            pres_l = lax.pmax(pres_l, AXIS)
            pres_r = lax.pmax(pres_r, AXIS)
            keep_l = lvalid & (jnp.take(pres_r, hl) > 0)
            keep_r = rvalid & (jnp.take(pres_l, hr) > 0)
            ltree_c, lcount = compact_valid(ltree, keep_l)
            rtree_c, rcount = compact_valid(rtree, keep_r)
            return (lcount[None, None].astype(jnp.int32),
                    rcount[None, None].astype(jnp.int32),
                    *[x[None] for x in jax.tree.leaves(ltree_c)],
                    *[x[None] for x in jax.tree.leaves(rtree_c)])

        return mex.smap(f, 2 + nl + len(rleaves))

    fn = mex.cached(key, build)
    out = fn(left.counts_device(), right.counts_device(),
             *lleaves, *rleaves)
    new_left = DeviceShards(mex, jax.tree.unflatten(
        ltd, list(out[2:2 + nl])), out[0])
    new_right = DeviceShards(mex, jax.tree.unflatten(
        rtd, list(out[2 + nl:])), out[1])
    return new_left, new_right


def _run_bounds(lw, lvalid, rw, rvalid):
    """For each right item: [lo, hi) bounds of its equal-key run among
    the sorted valid left items.

    O((L+R) log(L+R)): both sides' key words are sorted together with a
    side flag. With right sorting *after* equal left keys, a right item
    at combined position p has (p - #rights before) = #lefts with key
    <= its key = ``hi``; flipping the flag gives #lefts with key < its
    key = ``lo``. Invalid items sort last via a prepended validity word
    (not a key-word sentinel) and are excluded from the left counts, so
    they never perturb valid bounds even for all-ones keys.
    """
    lcap = lw[0].shape[0]
    rcap = rw[0].shape[0]
    # Validity is a *prepended sort word* (0 = valid, 1 = invalid), never
    # an overwrite of the key words: the all-ones sentinel would collide
    # with legitimate keys that encode to all-ones (uint64.max, all-0xFF
    # byte keys) and produce phantom pairs against padding garbage.
    valid_all = jnp.concatenate([lvalid, rvalid])
    invalid_word = (~valid_all).astype(jnp.uint32)

    from ...core.device_sort import argsort_words

    def counts_below(right_after: bool):
        side_l = jnp.zeros(lcap, jnp.uint64) if right_after else \
            jnp.ones(lcap, jnp.uint64)
        side_r = jnp.ones(rcap, jnp.uint64) if right_after else \
            jnp.zeros(rcap, jnp.uint64)
        words = [jnp.concatenate([a, b]) for a, b in zip(lw, rw)]
        side = jnp.concatenate([side_l, side_r])
        ridx = jnp.concatenate([jnp.full(lcap, rcap, jnp.uint64),
                                jnp.arange(rcap, dtype=jnp.uint64)])
        perm = argsort_words([invalid_word] + words + [side])
        side_s = jnp.take(side, perm)
        ridx_s = jnp.take(ridx, perm)
        valid_s = jnp.take(valid_all, perm)
        is_right = side_s == (1 if right_after else 0)
        is_left = ~is_right
        # valid lefts at positions <= p == valid lefts before a right item
        lefts_before = jnp.cumsum((is_left & valid_s).astype(jnp.int64))
        # scatter back to right-item order
        out = jnp.zeros(rcap + 1, jnp.int64)
        tgt = jnp.where(is_right, ridx_s.astype(jnp.int64), rcap)
        out = out.at[tgt].set(jnp.where(is_right, lefts_before, 0))
        return out[:rcap]

    hi = counts_below(right_after=True)
    lo = counts_below(right_after=False)
    return lo, hi


def _h(k):
    if isinstance(k, np.ndarray):
        return tuple(k.tolist())
    if isinstance(k, np.generic):
        return k.item()
    return k


def _enum_key(t):
    """Key of a position-enumerated (g, item) pair (dense host path)."""
    return t[0]


def InnerJoin(left: DIA, right: DIA, left_key_fn, right_key_fn,
              join_fn, location_detection=None,
              out_size_hint=None, dense_right_index=None) -> DIA:
    """``location_detection``: None (default) lets the plan-time cost
    model decide whether to pre-filter both sides by cross-side key
    presence before the shuffle (core/preshuffle.py; forced by
    THRILL_TPU_LOCATION_DETECT=0/1); True/False force it per call like
    the reference's LocationDetectionTag.

    ``out_size_hint``: optional per-worker upper bound on match
    count; lets the device path skip its blocking size sync. A wrong
    hint is SAFE: overflow is detected before any consumer reads the
    columns and the join phase transparently re-runs without the hint
    (lineage retry; ``event=recovery`` logged, counted in
    ``ctx.overall_stats()['join_overflow_retries']``). Set
    THRILL_TPU_JOIN_RECOVER=0 to raise instead of recovering — either
    way it never silently truncates.

    ``dense_right_index=n``: declares the right side a dense index
    table — exactly n rows globally, the row at global position g has
    key g (``table.ZipWithIndex(...)`` over a ReduceToIndex/Generate
    result). The join then runs as a pure device GATHER: no sort, no
    hash partition, no exchange, no size sync, at any worker count.
    Host-known right counts are validated against the dense layout;
    out-of-range left keys yield no pair (inner-join semantics)."""
    return DIA(InnerJoinNode(left.context, left._link(), right._link(),
                             left_key_fn, right_key_fn, join_fn,
                             location_detection=location_detection,
                             out_size_hint=out_size_hint,
                             dense_right_index=dense_right_index))
