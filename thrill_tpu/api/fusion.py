"""Cross-op program stitching: fuse device DOp chains into one dispatch.

The function-stack machinery (api/stack.py) already fuses chained
Map/Filter/FlatMap lambdas into one traced program, but every device
DOp still issued its OWN jitted dispatch, so a six-op pipeline paid
six launches and six sets of HBM round trips where one would do. This
module is
the cross-op generalization of the stack: at stage-build time the pull
recursion assembles a :class:`FusionPlan` — a chain of traced
:class:`Segment`s over one (or, for Zip/Join heads, several) input
``DeviceShards`` — and the whole chain compiles ONCE via
``MeshExec.cached()`` under a composite plan key and dispatches through
ONE ``smap`` call.

Mechanics, mirroring the reference's template function stacks
(thrill/api/dia.hpp:358-387) one level up the operator hierarchy:

* A fusible DOp implements ``compute_plan()`` (api/dia_base.py): pull
  the parent as a plan, append its own traced segment, hand the plan
  on. A sole-consumer parent in state NEW *defers* — its program is
  traced into the consumer's dispatch instead of running on its own
  (``materialize_plan``); anything else materializes normally and
  becomes a plan *source*.
* Fusion barriers: all-to-all exchanges, host fallbacks, spills,
  actions, multi-consumer results (``Keep``), and any op without a
  traced segment. A barrier simply ends the chain — the plan executes
  and its output shards seed the next chain.
* State inside a stitched program is ``(tree, mask)`` exactly like the
  stack contract; the final program compacts valid rows once and
  returns device-resident counts. Cross-worker plan values that the
  legacy per-op programs fetched via host counts (ZipWithIndex offsets,
  Window halos) are computed IN-TRACE from collectives over the mask,
  so fused chains need no mid-chain host syncs at all.
* PR-1 failure semantics are preserved: the dispatch retries transient
  faults under the shared policy (the program is pure), every fused
  segment keeps a per-op fault site (``api.fuse.<OpLabel>``), and
  deferred validations (hinted-join overflow) attach to the fused
  program's OUTPUT — checks drain at the fused boundary, recovery
  re-dispatches the plan at the true capacity (lineage = the plan's
  immutable sources).

``THRILL_TPU_FUSE=0`` restores the exact per-op dispatch behavior
(every code path falls back to the pre-fusion implementations).
Observability: ``stats_fused_dispatches`` / ``stats_fused_ops`` on the
mesh, per-stage fused-op lists as ``event=fused_dispatch`` JSON lines,
both surfaced by ``ctx.overall_stats()`` and tools/json2profile.py.
"""

from __future__ import annotations

import dataclasses
import os
import re
import weakref
from typing import Any, Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ..common import decisions as _decisions
from ..common import faults
from ..common import trace as _trace
from ..common.retry import default_policy
from ..core import segmented
from ..core.jaxpr_deps import output_deps
from ..data.shards import DeviceShards, HostShards, compact_valid
from ..parallel.mesh import AXIS
from .stack import (Stack, apply_stack_host_list, apply_stack_traced,
                    stack_bound_operands, stack_cache_token)


def enabled() -> bool:
    """THRILL_TPU_FUSE=0 restores per-op dispatches exactly."""
    return os.environ.get("THRILL_TPU_FUSE", "1") not in ("0", "off",
                                                          "false")


class TraceCtx:
    """Per-trace context handed to segment trace functions."""

    def __init__(self, W: int) -> None:
        self.W = W
        self.aux: dict = {}          # name -> per-worker scalar output
        # the index plan of the segment being traced
        # (Segment.index_plan), or None
        self.index_plan: Optional[Tuple] = None

    @staticmethod
    def count(mask: jnp.ndarray) -> jnp.ndarray:
        return jnp.sum(mask.astype(jnp.int32))

    def exclusive_offset(self, mask: jnp.ndarray) -> jnp.ndarray:
        """Global item offset of this worker's valid items, computed
        in-trace (an all_gather of local counts — the fused analog of
        the host-counts prefix the legacy per-op programs uploaded)."""
        cnt = jnp.sum(mask.astype(jnp.int64))
        if self.W == 1:
            return jnp.int64(0)
        totals = lax.all_gather(cnt, AXIS)              # [W]
        widx = lax.axis_index(AXIS)
        return jnp.where(jnp.arange(self.W) < widx, totals, 0).sum()

    def emit_aux(self, name: str, value: jnp.ndarray) -> None:
        """Expose a per-worker SCALAR as an extra program output (e.g.
        a hinted join's true match totals for the deferred check)."""
        self.aux[name] = value


@dataclasses.dataclass
class Segment:
    """One fusible device-DOp phase, traceable into a stitched program.

    ``trace(fctx, tree, mask, bound)`` runs per worker inside shard_map
    and returns the new ``(tree, mask)``; collectives over AXIS are
    allowed. Head segments (multi-input ops) instead receive the list
    of source ``(tree, mask)`` states. ``bound`` carries the traced
    form of :attr:`bound` (runtime pytrees entering the program as
    replicated arguments — the Bind contract, so iterative re-binds
    never recompile).
    """

    label: str
    token: Tuple
    trace: Callable
    bound: Tuple = ()
    # output counts == input counts (all-map stacks, ZipWithIndex...):
    # lets the plan hand host-known counts through, like the legacy
    # apply_stack_device counts passthrough
    preserves_counts: bool = False
    # output already has all valid rows in a prefix (sorts, a dense
    # ReduceToIndex range, ReduceByKey's fold, which gathers one row
    # per run): the final compaction scatter is skipped
    already_compact: bool = False
    # output row j is input row j's and the mask passes through (a
    # scan over the rows): with ``preserves_counts``, a prefix of valid
    # rows stays one, as behind a map-only stack
    keeps_rows: bool = False
    # host-known output counts this segment imposes (ReduceToIndex's
    # dense range sizes); replaces the plan's known counts
    sets_counts: Optional[np.ndarray] = None
    # multi-input head refit hook: rebuild this segment with a new
    # static output capacity (hinted-join overflow recovery)
    refit: Optional[Callable[[int], "Segment"]] = None
    # called by execute() with (plan, out_shards): attaches deferred
    # checks (hinted-join overflow) to the fused boundary
    finalize: Optional[Callable[["FusionPlan", DeviceShards], None]] = None
    dia_id: Optional[int] = None
    # every output row derives from exactly one input row (LOp stacks:
    # map/filter/flatmap — no collectives, no cross-row state), so the
    # memory-pressure ladder may re-plan the chain as row-range
    # sub-dispatches (mem/pressure.py rung 3) without changing results
    row_local: bool = False
    # may emit MORE rows than it consumes (flat_map): the admission
    # cost model must not bound this chain's output by its input bytes
    expands: bool = False
    # host-engine form of this segment (items list -> items list); the
    # ladder's LAST rung runs the chain through these when even split
    # chunks exhaust HBM
    host_apply: Optional[Callable] = None
    # ``index_plan(fctx, tree, mask, bound)`` -> a tuple of per-worker
    # arrays, or None: what this segment derives from the index column
    # and the mask of the state it is handed, reading no value
    # (ReduceToIndex's fold over sorted runs). ``trace`` finds it in
    # ``fctx.index_plan``. The stitched program computes it in place;
    # :func:`index_plans` has it also as a program of its own, for a
    # whole-loop program (api/loop.py) whose carry it does not read
    index_plan: Optional[Callable] = None


@dataclasses.dataclass(frozen=True)
class IndexPlans:
    """The index plans (Segment.index_plan) that one run of a compiled
    program computes in place."""
    count: int
    # of them, the plans of a dense fold (core/segmented.py
    # DenseFoldPlan), counted in ``r2i_dense_plans`` too
    dense: int = 0
    # each plan as a program of its own: (the positions of the
    # program's arguments that it reads, the shard_map program over
    # just those)
    plans: Tuple[Tuple[Tuple[int, ...], Callable], ...] = ()
    # the program in the form that takes the plans' outputs as further
    # arguments behind its own, in place of computing them; None where
    # there is no such form (the per-op path)
    body: Optional[Callable] = None


# raw program -> IndexPlans, for the programs that compute any
_INDEX_PLANS: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def note_index_plans(fn, info: IndexPlans) -> None:
    _INDEX_PLANS[fn.raw] = info
    fn.index_plans = info.count     # counted by every dispatch of it
    fn.dense_plans = info.dense


def index_plans(fn) -> Optional[IndexPlans]:
    """What the compiled program ``fn`` computes of index plans, or
    None. Keyed by the raw program, which a tape records (api/loop.py
    asks with the recorded call, whatever twin of it a replay runs)."""
    raw = getattr(fn, "raw", None)
    return None if raw is None else _INDEX_PLANS.get(raw)


def _src_sig(shards: DeviceShards, flat) -> Tuple:
    leaves, treedef = flat
    return (shards.cap, treedef,
            tuple((jnp.dtype(l.dtype), l.shape[2:]) for l in leaves))


class FusionPlan:
    """A pending chain of traced segments over source DeviceShards.

    ``head`` (optional) consumes ALL sources (Zip/Join); the tail
    segments are linear. ``stitchable=False`` marks a plain wrapper
    around already-computed shards (host storage, or fusion disabled)
    — ``finish()`` then just unwraps.
    """

    def __init__(self, mesh_exec, sources: List[Any],
                 head: Optional[Segment] = None,
                 stitchable: bool = True,
                 known_counts: Optional[np.ndarray] = None) -> None:
        self.mex = mesh_exec
        self.sources = sources
        self.head = head
        self.segments: List[Segment] = []
        # the THRILL_TPU_FUSE=0 escape hatch gates stitchability at the
        # root: every wrapped plan then refuses segments and each op
        # falls back to its per-op dispatch path exactly
        self.stitchable = stitchable and enabled() and all(
            isinstance(s, DeviceShards) for s in sources)
        if head is not None:
            known_counts = head.sets_counts if head.sets_counts is not None \
                else known_counts
        elif known_counts is None and self.stitchable \
                and len(sources) == 1:
            known_counts = sources[0]._counts_host
        self.known_counts = known_counts
        self.aux: dict = {}          # last execute()'s aux outputs
        self._no_finalize = False    # recovery re-runs skip finalizers
        self._no_split = False       # split-rung chunks must not re-split

    # -- building -------------------------------------------------------
    def append(self, seg: Segment) -> None:
        assert self.stitchable, "cannot extend a non-stitchable plan"
        self.segments.append(seg)
        if seg.sets_counts is not None:
            self.known_counts = seg.sets_counts
        elif not seg.preserves_counts:
            self.known_counts = None

    @property
    def all_segments(self) -> List[Segment]:
        return ([self.head] if self.head is not None else []) \
            + self.segments

    def rows_are_a_known_prefix(self) -> bool:
        """At the end of the pending chain every worker's valid rows
        are a prefix whose length the host knows: a source's are, a
        head's that says so and hands its counts over (Zip), and every
        tail segment keeps them. What a Window needs to fuse (its halo
        rule reads the counts, its trace takes rows ``0 .. count-1``)."""
        return self.stitchable and self.known_counts is not None \
            and (self.head is None or self.head.already_compact) \
            and all(s.preserves_counts for s in self.segments)

    # -- execution ------------------------------------------------------
    def finish(self):
        """Produce this plan's shards (host or device) for NON-TRACED
        consumption. This is the fused boundary: deferred checks a
        segment attached (hinted-join overflow) drain HERE, before any
        consumer — exchange plan step, action egress, host fallback —
        can read the columns (the unfused pull's validate_pending
        invariant, dia_base.ParentLink._pull_unfused)."""
        if not self.stitchable:
            return self.sources[0]
        shards = self.execute()
        shards.validate_pending()
        return shards

    def execute(self) -> DeviceShards:
        mex = self.mex
        segs = self.all_segments
        if not segs:
            return self.sources[0]
        tr = getattr(mex, "tracer", None)
        if tr is None or not tr.enabled:
            return self._execute_inner()
        # one span per stitched launch: the chunk/dispatch spans nest
        # under it, so a Perfetto lane shows which ops each dispatch
        # carried (trace kinds: cat "fusion")
        with tr.span("fusion",
                     "+".join(s.label for s in segs)[:120],
                     ops=len(segs)):
            return self._execute_inner()

    def _execute_inner(self) -> DeviceShards:
        mex = self.mex
        srcs = self.sources
        segs = self.all_segments
        # exchange-boundary scheduling: a source produced by an
        # OPTIMISTIC exchange (data/exchange.py capacity-plan cache)
        # still owes its deferred capacity check — run it before this
        # program bakes the source columns. The check blocks only until
        # the exchange's FIRST chunk lands (the overflow flag rides
        # chunk 0), so the stitched program here is enqueued while the
        # remaining chunks' collectives are still in flight — that is
        # the chunk-pipeline overlap, with none of the wrong-data risk
        for s in srcs:
            s.validate_pending()
        src_flat = [jax.tree.flatten(s.tree) for s in srcs]
        sigs = tuple(_src_sig(s, f) for s, f in zip(srcs, src_flat))
        bound_flat = []
        bound_sig = []
        for seg in segs:
            bl, bt = jax.tree.flatten(seg.bound)
            bl = mex.asarray_blessed(bl)
            bound_flat.append((bl, bt))
            bound_sig.append((bt, tuple((jnp.dtype(l.dtype),
                                         tuple(l.shape)) for l in bl)))
        key = ("fused", sigs, tuple(s.token for s in segs),
               tuple(bound_sig))
        holder: dict = {}
        W = mex.num_workers
        caps = [s[0] for s in sigs]
        head, tail = self.head, self.segments
        # a source's valid rows are a prefix (its mask is made from its
        # count), so are those behind a segment that says so, and a
        # map-only stack keeps them where they are: no compaction
        # scatter then (72 ns per row and 8-byte leaf on a v5e). Rows
        # past the count hold whatever the maps made of the padding
        compact = head is None
        for seg in segs:
            compact = seg.already_compact or (
                compact and seg.preserves_counts
                and (seg.row_local or seg.keeps_rows))
        nd = len(srcs) + sum(len(f_[0]) for f_ in src_flat)
        nb = sum(len(bf[0]) for bf in bound_flat)
        in_specs = (P(AXIS),) * nd + (P(),) * nb

        def chain(fctx, args, stop, pre):
            """Trace the chain from the flat arguments up to tail
            segment ``stop`` (exclusive): the ``(tree, mask)`` there and
            the traced bounds of the tail. ``pre[k]`` is tail segment
            ``k``'s index plan where that ran before this program; any
            other is computed here."""
            nsrc = len(srcs)
            counts = args[:nsrc]
            pos = nsrc
            states = []
            for k, (leaves_, td_) in enumerate(src_flat):
                ls = args[pos:pos + len(leaves_)]
                pos += len(leaves_)
                tree = jax.tree.unflatten(td_, [l[0] for l in ls])
                mask = jnp.arange(caps[k]) < counts[k][0, 0]
                states.append((tree, mask))
            bounds_t = []
            for bl, bt in bound_flat:
                bs = args[pos:pos + len(bl)]
                pos += len(bl)
                bounds_t.append(jax.tree.unflatten(bt, list(bs)))
            if head is not None:
                tree, mask = head.trace(fctx, states, bounds_t[0])
                bounds_t = bounds_t[1:]
            else:
                tree, mask = states[0]
            for k, (seg, bound_t) in enumerate(zip(tail[:stop],
                                                   bounds_t)):
                if k in pre:
                    fctx.index_plan = pre[k]
                else:
                    fctx.index_plan = seg.index_plan and seg.index_plan(
                        fctx, tree, mask, bound_t)
                tree, mask = seg.trace(fctx, tree, mask, bound_t)
            return tree, mask, bounds_t

        args = ([s.counts_device() for s in srcs]
                + [l for f_ in src_flat for l in f_[0]]
                + [l for bf in bound_flat for l in bf[0]])

        def program(split):
            """The stitched program. ``split`` lists (tail index, number
            of arrays) of the index plans that arrive as sharded
            arguments behind the bounds, in place of being computed
            here: the form a loop runs with the plans hoisted."""
            def f(*args):
                pos = nd + nb
                pre_t = {}
                for k, n_k in split:
                    pre_t[k] = tuple(a[0] for a in args[pos:pos + n_k])
                    pos += n_k
                fctx = TraceCtx(W)
                tree, mask, _ = chain(fctx, args, len(tail), pre_t)
                if compact:
                    out_tree = tree
                    new_count = jnp.sum(mask.astype(jnp.int32))
                else:
                    out_tree, new_count = compact_valid(tree, mask)
                out_leaves, out_td = jax.tree.flatten(out_tree)
                holder["treedef"] = out_td
                holder["n_out"] = len(out_leaves)
                holder["aux_names"] = tuple(sorted(fctx.aux))
                return (new_count[None, None].astype(jnp.int32),
                        *[l[None] for l in out_leaves],
                        *[fctx.aux[n][None, None]
                          for n in holder["aux_names"]])

            n_pre = sum(n_k for _, n_k in split)
            # the program's name on the device plane says which ops it
            # carries: jit_fused_Sort, jit_fused_ReduceByKey.pre_...
            name = re.sub(r"[^A-Za-z0-9_.]+", "_", "fused_" + "_".join(
                s.label for s in segs))[:64]
            return mex.smap(f, nd + nb + n_pre,
                            in_specs=in_specs + (P(AXIS),) * n_pre,
                            name=name)

        def build():
            fn = program(())
            plans = self._index_plan_programs(args, nd, in_specs, chain)
            if plans:
                note_index_plans(fn, IndexPlans(
                    count=len(plans),
                    dense=sum(dense for _, _, _, _, dense in plans),
                    plans=tuple((used, raw) for _, _, used, raw, _ in plans),
                    body=program([(k, n_k)
                                  for k, n_k, _, _, _ in plans]).raw))
            return fn, holder

        fn, h = mex.cached(key, build)
        split = self._proactive_split(fn, srcs, segs)
        if split is not None:
            return split
        if faults.REGISTRY.active():
            # per-op fault sites survive fusion: each constituent op
            # keeps a named site, and a transient fire at the stage
            # boundary retries under the shared policy. The dispatch
            # itself stays OUTSIDE this policy — _CountedJit already
            # retries api.mesh.dispatch under its own run, and nesting
            # the two would multiply the documented attempt budget
            # (4 -> 16) for dispatch faults inside stitched programs,
            # silently diverging from the THRILL_TPU_FUSE=0 path
            def site_checks():
                for seg in segs:
                    faults.check("api.fuse." + seg.label,
                                 dia_id=seg.dia_id, fused_ops=len(segs))

            default_policy().run(site_checks, what="fuse.dispatch")
        pres = mex.pressure
        if pres is not None and pres.enabled \
                and not any(s.expands for s in segs) \
                and getattr(fn, "_out_bytes", None) is None:
            # cost-model hint from the plan's shapes: a non-expanding
            # chain produces at most its sources' rows, so the sources'
            # leaf bytes bound the stitched program's output. Expanding
            # chains (flat_map) skip the hint — the learned per-program
            # size / factor guess handles them instead of a systematic
            # underestimate on exactly the chains most likely to OOM.
            # Once the program LEARNED its measured output size (this
            # process, or imported from the plan store on a warm
            # restart), that exact number governs instead of this
            # upper bound — a fused ReduceByKey's output is usually
            # far smaller than its sources
            pres.hint_output_bytes(sum(
                int(getattr(l, "nbytes", 0) or 0)
                for s in srcs for l in jax.tree.leaves(s.tree)))
        # decision ledger: the fusion split point — which ops ride this
        # one dispatch, and what the cost model predicts its output
        # weighs (audited below against the measured output leaves)
        led = _decisions.ledger_of(mex)
        dec = None
        if led is not None:
            ops_label = "+".join(s.label for s in segs)[:80]
            pred = getattr(fn, "_out_bytes", None)
            why = "learned output size"
            if pred is None and not any(s.expands for s in segs):
                pred = sum(int(getattr(l, "nbytes", 0) or 0)
                           for s in srcs
                           for l in jax.tree.leaves(s.tree))
                why = "non-expanding chain: bounded by source bytes"
            elif pred is None:
                why = "expanding chain: no bound"
            dec = led.record("fusion", "fuse:" + ops_label, "fuse",
                             predicted=pred, reason=why,
                             ops=ops_label, n_ops=len(segs),
                             dia_ids=[s.dia_id for s in segs])
        try:
            out = fn(*args)
        except Exception as e:
            # rungs 3-4 of the memory-pressure ladder (mem/pressure.py):
            # the dispatch choke point already spilled and retried —
            # an OOM surfacing here means the segment chain itself does
            # not fit, so re-plan it as row-range sub-dispatches (or,
            # last, run the chain's host-engine form)
            from ..mem import pressure as _pressure
            if self._no_split or not (_pressure.retry_enabled()
                                      and _pressure.is_oom_error(e)):
                raise
            return self._execute_degraded(e)
        mex.stats_fused_dispatches += 1
        mex.stats_fused_ops += len(segs)
        ops = tuple(s.label for s in segs)
        counts_map = getattr(mex, "fused_stage_counts", None)
        if counts_map is not None:
            counts_map[ops] = counts_map.get(ops, 0) + 1
        log = getattr(mex, "logger", None)
        if log is not None and log.enabled:
            log.line(event="fused_dispatch", ops=list(ops),
                     dia_ids=[s.dia_id for s in segs])
        n_out = h["n_out"]
        if dec is not None:
            led.resolve(dec, sum(int(getattr(l, "nbytes", 0) or 0)
                                 for l in out[1:1 + n_out]))
        tree = jax.tree.unflatten(h["treedef"], list(out[1:1 + n_out]))
        self.aux = dict(zip(h["aux_names"], out[1 + n_out:]))
        if self.known_counts is not None:
            shards = DeviceShards(mex, tree, self.known_counts.copy())
        else:
            shards = DeviceShards(mex, tree, out[0])
        if not self._no_finalize:
            for seg in segs:
                if seg.finalize is not None:
                    seg.finalize(self, shards)
        return shards

    def _index_plan_programs(self, args, nd, in_specs, chain) -> list:
        """The index plans of this chain's tail segments
        (Segment.index_plan) as programs of their own: ``(tail index,
        number of outputs, the positions of the arguments it reads, the
        shard_map program over just those, 1 for a dense fold's plan
        else 0)`` for each that yields one.

        A plan is traced behind the chain up to its segment, and the
        arguments it reads are those its outputs can depend on
        (core/jaxpr_deps.py)."""
        mex = self.mex
        avals = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args]
        out = []
        for k, seg in enumerate(self.segments):
            if seg.index_plan is None:
                continue
            n_out = []

            def g(*a, k=k, seg=seg, n_out=n_out):
                fctx = TraceCtx(mex.num_workers)
                tree, mask, bounds_t = chain(fctx, a, k, {})
                res = seg.index_plan(fctx, tree, mask, bounds_t[k]) or ()
                n_out[:] = [len(res),
                            int(isinstance(res, segmented.DenseFoldPlan))]
                return tuple(r[None] for r in res)

            closed = jax.make_jaxpr(
                mex.smap(g, len(args), in_specs=in_specs).raw)(*avals)
            if not n_out[0]:
                continue
            used = tuple(sorted(frozenset().union(
                *output_deps(closed.jaxpr))))

            def pruned(*sub, g=g, used=used):
                # inside shard_map a sharded argument is its worker's
                # [1, ...] block; what the outputs cannot depend on is
                # dead code whatever stands in for it
                a = [jnp.zeros(((1,) + v.shape[1:]) if i < nd
                               else v.shape, v.dtype)
                     for i, v in enumerate(avals)]
                for i, x in zip(used, sub):
                    a[i] = x
                return g(*a)

            out.append((k, n_out[0], used, mex.smap(
                pruned, len(used),
                in_specs=tuple(in_specs[i] for i in used)).raw, n_out[1]))
        return out

    def reexecute(self, new_cap: int) -> DeviceShards:
        """Recovery re-dispatch with the head refit to ``new_cap``
        (hinted-join overflow): same sources, same tail, finalizers
        suppressed so checks are not re-attached."""
        assert self.head is not None and self.head.refit is not None
        plan = FusionPlan(self.mex, self.sources,
                          head=self.head.refit(new_cap))
        plan.segments = list(self.segments)
        plan.known_counts = None
        plan._no_finalize = True
        return plan.execute()

    def _proactive_split(self, fn, srcs, segs):
        """Planner-chosen fusion split point under the HBM admission
        estimate (api/planner.py): a row-local single-source chain
        whose estimated input+output bytes cannot fit under the
        watermark at ANY spill level executes as K row-range
        sub-dispatches up front — the same sub-plan the OOM ladder's
        rung 3 would reach, chosen BEFORE the dispatch instead of
        after a retry budget's worth of failed allocations. Returns
        the split result, or None (dispatch whole — the normal path).
        Eligibility mirrors ``_execute_degraded`` exactly: what the
        reactive rung could not split, the planner must not either."""
        from .planner import planner_of
        mex = self.mex
        pl = planner_of(mex)
        pres = mex.pressure
        if pl is None or pres is None or not pres.enabled \
                or self._no_split or self.head is not None \
                or len(srcs) != 1 \
                or getattr(mex, "num_processes", 1) > 1 \
                or not all(s.row_local and s.finalize is None
                           for s in segs):
            return None
        from ..mem import pressure as _pressure
        if not _pressure.retry_enabled():
            return None
        src = srcs[0]
        src_bytes = sum(int(getattr(l, "nbytes", 0) or 0)
                        for l in jax.tree.leaves(src.tree))
        out_est = getattr(fn, "_out_bytes", None)
        if out_est is None:
            out_est = (src_bytes if not any(s.expands for s in segs)
                       else int(src_bytes * pres.est_factor))
        est = src_bytes + int(out_est)
        k = pl.fusion_split_k(est, src.cap)
        if k is None:
            return None
        try:
            out = self._execute_split(src, k)
        except Exception as e:
            if not _pressure.is_oom_error(e):
                raise
            # even the split chunks exhausted HBM: dispatch whole and
            # let the reactive ladder (rungs 2-4) own the escalation —
            # the planner's choice is advisory, never the last word
            faults.note("recovery", what="mem.split_oom",
                        ops=[s.label for s in segs],
                        error=repr(e)[:200])
            return None
        # recorded AFTER the split succeeded: a fallback-to-whole must
        # not leave a ledger record claiming split:K for a dispatch
        # that actually ran whole (the whole path records its own
        # `fusion` decision). Deliberately NOT a planner_switches tick:
        # a chain that stays inadmissible re-splits on every execute —
        # that is a standing choice, not a re-optimization.
        ops_label = "+".join(s.label for s in segs)[:80]
        led = _decisions.ledger_of(mex)
        if led is not None:
            led.record("fusion_split", "fuse:" + ops_label,
                       f"split:{k}", predicted=est // k,
                       rejected=[("whole", est)],
                       reason="admission estimate exceeds the HBM "
                              "watermark at any spill level",
                       ops=ops_label, k=k,
                       dia_ids=[s.dia_id for s in segs])
        pres.segment_splits += 1
        faults.note("segment_split", k=k,
                    ops=[s.label for s in segs], cap=src.cap,
                    proactive=True)
        faults.note("recovery", what="mem.segment_split_proactive",
                    _quiet=True)
        _trace.instant_of(getattr(mex, "tracer", None), "mem",
                          "segment_split", k=k, proactive=True)
        return out

    # -- memory-pressure degradation (mem/pressure.py rungs 3-4) --------
    def _execute_degraded(self, exc: BaseException):
        """The stitched dispatch exhausted the OOM-retry budget:
        escalate. Rung 3 re-plans a row-local single-source chain as K
        row-range sub-dispatches (``event=segment_split`` — lineage-
        level like the hinted-join overflow re-run, never wrong data);
        rung 4 runs the chain's host-engine form. Multi-controller
        meshes re-raise: degradation is a per-process decision, and an
        asymmetric re-plan would desynchronize the collective
        schedule across controllers (same reasoning as the governor's
        multi-process spill guard)."""
        from ..mem import pressure as _pressure
        mex = self.mex
        segs = self.all_segments
        labels = [s.label for s in segs]
        if getattr(mex, "num_processes", 1) > 1 or self.head is not None \
                or len(self.sources) != 1:
            raise exc
        pres = _pressure._monitor_for(mex)
        src = self.sources[0]
        if all(s.row_local and s.finalize is None for s in segs):
            k = _pressure.split_k(src.cap)
            if src.cap > 1:
                try:
                    out = self._execute_split(src, k)
                except Exception as e2:
                    if not _pressure.is_oom_error(e2):
                        raise
                    faults.note("recovery", what="mem.split_oom",
                                ops=labels, error=repr(e2)[:200])
                else:
                    pres.segment_splits += 1
                    faults.note("segment_split", k=k, ops=labels,
                                cap=src.cap)
                    faults.note("recovery", what="mem.segment_split",
                                _quiet=True)
                    _trace.instant_of(getattr(mex, "tracer", None),
                                      "mem", "segment_split", k=k)
                    return out
        if all(s.host_apply is not None for s in segs):
            # last rung: the host engine (the reference's EM
            # degradation — slower, unbounded by HBM, bit-identical)
            pres.host_fallbacks += 1
            faults.note("recovery", what="mem.host_fallback",
                        ops=labels)
            _trace.instant_of(getattr(mex, "tracer", None), "mem",
                              "host_fallback", ops=len(labels))
            shards = src.to_host_shards(reason="memory_pressure")
            lists = shards.lists
            for seg in segs:
                lists = [seg.host_apply(items) for items in lists]
            return HostShards(shards.num_workers, lists)
        raise exc

    def _execute_split(self, src: DeviceShards, k: int) -> DeviceShards:
        """Run the (row-local) segment chain as ``k`` row-range
        sub-dispatches over ``common/partition.py`` bounds and
        reassemble per-worker results in chunk order — identical to
        the unsplit program because every output row derives from
        exactly one input row and chunk-then-compact preserves input
        order."""
        from ..common.partition import dense_range_bounds
        mex = self.mex
        bounds = dense_range_bounds(src.cap, k)
        counts = src.counts                 # host sync: degraded path
        parts: List[List[Any]] = [[] for _ in range(mex.num_workers)]
        for i in range(k):
            lo, hi = int(bounds[i]), int(bounds[i + 1])
            if hi <= lo:
                continue
            chunk_tree = jax.tree.map(lambda l: l[:, lo:hi], src.tree)
            chunk = DeviceShards(
                mex, chunk_tree,
                np.clip(counts - lo, 0, hi - lo).astype(np.int64))
            sub = FusionPlan(mex, [chunk])
            sub.segments = list(self.segments)
            sub.known_counts = None
            sub._no_finalize = True
            sub._no_split = True
            out_k = sub.execute()
            for w, t in enumerate(out_k.to_worker_arrays()):
                parts[w].append(t)
        per_worker = [jax.tree.map(
            lambda *ls: np.concatenate([np.asarray(l) for l in ls],
                                       axis=0), *p) for p in parts]
        return DeviceShards.from_worker_arrays(mex, per_worker)


def wrap(shards) -> FusionPlan:
    """Plan-shaped wrapper around computed shards (host or device)."""
    mex = getattr(shards, "mesh_exec", None)
    return FusionPlan(mex, [shards],
                      stitchable=isinstance(shards, DeviceShards))


def stack_segment(stack: Stack, dia_id: Optional[int] = None) -> Segment:
    """The LOp function stack as a fused segment (same traced math as
    api/device_exec.apply_stack_device, minus its own dispatch)."""
    bound = tuple(stack_bound_operands(stack))

    def trace(fctx, tree, mask, bound_t):
        return apply_stack_traced(tree, mask, stack,
                                  bound=list(bound_t) if bound_t
                                  else None)

    return Segment(label="Stack",
                   token=("stack", stack_cache_token(stack)),
                   trace=trace, bound=bound,
                   preserves_counts=all(op.kind == "map" for op in stack),
                   dia_id=dia_id, row_local=True,
                   expands=any(op.kind == "flat_map" for op in stack),
                   host_apply=lambda items, _s=stack:
                       apply_stack_host_list(items, _s))


def pull_plan(link, consume: bool = True) -> FusionPlan:
    """Pull a parent edge as a fusion plan.

    The fused counterpart of ``ParentLink.pull``: the parent either
    defers (its segments arrive pending in the plan) or materializes
    (its shards become the plan source, deferred validations drained at
    this boundary); the edge's LOp stack joins the chain as a segment.
    With fusion disabled this is exactly ``wrap(link.pull())``.
    """
    if not enabled():
        return wrap(link.pull(consume))
    res = link.node.materialize_plan(consume=consume)
    if isinstance(res, FusionPlan):
        plan = res
    elif isinstance(res, DeviceShards):
        # overflow checks drain at the fused boundary (the legacy
        # pull's validate_pending contract)
        res.validate_pending()
        plan = FusionPlan(res.mesh_exec, [res])
    else:
        plan = wrap(res)
    if link.stack:
        if plan.stitchable:
            plan.append(stack_segment(link.stack, dia_id=link.node.id))
        else:
            shards = plan.finish()
            if isinstance(shards, HostShards):
                shards = HostShards(shards.num_workers,
                                    [apply_stack_host_list(l, link.stack)
                                     for l in shards.lists])
            else:                      # pragma: no cover — defensive
                from .device_exec import apply_stack_device
                shards = apply_stack_device(shards, link.stack)
            plan = wrap(shards)
    return plan
