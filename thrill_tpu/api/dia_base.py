"""DIA graph nodes and the stage driver.

Equivalent of the reference's DIABase / DIANode / StageBuilder
(reference: thrill/api/dia_base.hpp:87 states NEW/EXECUTED/DISPOSED,
dia_base.cpp:302-442 FindStages + toposort + Execute/PushData per stage,
dia_node.hpp:123-177 RunPushData / consume counters).

Single-controller translation: an action triggers ``materialize()`` on
its parents, which recursively executes ancestor nodes in deterministic
node-id order (the recursion *is* the reference's BFS-up + toposort,
since ids increase in construction order and parents always precede
children). Results cache on the node (state EXECUTED) until disposed;
``Keep()`` raises the consume budget exactly like the reference's
consume counters, so memory can be reclaimed mid-pipeline.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, List, Optional, Sequence, Tuple, Union

from ..common.trace import span_of
from ..data.shards import DeviceShards, HostShards
from .stack import Stack, apply_stack_host_list, stack_cache_token

Shards = Union[DeviceShards, HostShards]

NEW = "NEW"
EXECUTED = "EXECUTED"
DISPOSED = "DISPOSED"
# the node's program was traced into its sole consumer's stitched
# dispatch (api/fusion.py) — consumed without ever materializing
FUSED = "FUSED"


@dataclasses.dataclass
class ParentLink:
    """A DOp's link to a parent node plus the LOp stack fused on the edge."""
    node: "DIABase"
    stack: Stack

    def pull(self, consume: bool = True) -> Shards:
        from . import fusion
        if fusion.enabled():
            # fused pull: upstream chains deferred into one stitched
            # dispatch execute here; the edge stack rides along instead
            # of paying its own dispatch
            return fusion.pull_plan(self, consume=consume).finish()
        return self._pull_unfused(consume)

    def _pull_unfused(self, consume: bool = True) -> Shards:
        shards = self.node.materialize(consume=consume)
        if isinstance(shards, DeviceShards):
            # deferred producer validations (hinted-join overflow) run
            # BEFORE any consumer — downstream op or action — reads the
            # columns: a recovering check heals shards.tree in place,
            # so truncation can neither propagate nor be consumed
            shards.validate_pending()
        if not self.stack:
            return shards
        if isinstance(shards, HostShards):
            return HostShards(shards.num_workers,
                              [apply_stack_host_list(l, self.stack)
                               for l in shards.lists])
        from .device_exec import apply_stack_device
        return apply_stack_device(shards, self.stack)

    def cache_token(self) -> Tuple:
        return (self.node.id, stack_cache_token(self.stack))


def staged_action(action):
    """An action ``action(dia, ...)`` under a ``stage`` span named for
    it: the root of its pipeline's spans, so that what the action does
    after its pull (a counts fetch, the egress) has a stage too."""

    @functools.wraps(action)
    def run(dia, *args, **kwargs):
        with dia.node.stage_span(action.__name__):
            return action(dia, *args, **kwargs)

    return run


class DIABase:
    """A node of the DIA dataflow DAG."""

    def __init__(self, ctx, label: str,
                 parents: Sequence[ParentLink] = ()) -> None:
        self.context = ctx
        self.label = label
        self.parents: List[ParentLink] = list(parents)
        self.id = ctx._register_node(self)
        # the pipeline this node belongs to: a source starts one, every
        # other node joins its parents' oldest. The stage spans carry
        # it, so that the spans of one Distribute -> ... -> action
        # share an identifier (common/trace.py)
        self.pipe = min((p.node.pipe for p in self.parents),
                        default=self.id)
        self.state = NEW
        self._shards: Optional[Shards] = None
        # number of remaining consuming pulls before data is freed; every
        # node's data may be used once, .Keep(n) allows n more uses
        # (reference: consume counters, api/dia_base.hpp:226-250)
        self.consume_budget = 1
        # host-RAM grant for this node's compute, set by the stage
        # driver from mem_use() before compute() runs (reference:
        # DIAMemUse negotiation, api/dia_base.cpp:121-270). None =
        # nothing requested/granted.
        self.mem_limit: Optional[int] = None

    # -- overridables ---------------------------------------------------
    # memory appetite of compute(): None = negligible, "max" = wants as
    # much as available (EM operators: Sort runs, GroupBy tables), an
    # int = fixed bytes (reference: DIAMemUse, api/dia_base.hpp:51)
    MEM_USE = None

    def mem_use(self):
        return self.MEM_USE

    def compute(self) -> Shards:
        """Produce this node's output shards (the DOp main op + push)."""
        raise NotImplementedError

    def compute_plan(self):
        """Fusible DOps override: return a :class:`fusion.FusionPlan`
        whose tail carries this node's traced segment (so a consumer
        can stitch it into one dispatch), or None when statically
        ineligible. Implementations that pull parents must ALWAYS
        return a plan afterwards (wrapping an eagerly computed result
        when the input turned out host-resident) — the pull consumed
        the parent."""
        return None

    # -- driver ---------------------------------------------------------
    def stage_span(self, name: Optional[str] = None):
        """The ``stage`` span of work done for this node: the parent
        of every upload, dispatch, wait and fetch underneath, so that a
        stage's self time is its duration minus its children's. Pulls
        nest by the pull recursion. ``name`` is an action's, which
        works on this node's output. A stage span with no span open
        above it is the root of a PULL (an action's, a loop's): counted
        as ``overall_stats()["pulls"]``, how often a job sent the pull
        recursion and the planner off."""
        tr = getattr(self.context, "tracer", None)
        if tr is not None and tr.enabled and tr.current_id() is None:
            self.context.mesh_exec.stats_pulls += 1
        return span_of(tr, "stage", name or self.label, dia_id=self.id,
                       pipe=self.pipe)

    def _barrier_decision(self, reason: str) -> None:
        """Ledger entry for a declined fusion deferral: WHY this node
        ends the stitched chain (common/decisions.py; explain() shows
        the barrier reason on the node)."""
        from ..common import decisions as _decisions
        led = _decisions.ledger_of(self.context.mesh_exec)
        if led is not None:
            led.record("fusion_barrier",
                       f"node:{self.label}#{self.id}", "materialize",
                       rejected=[("defer", None)], reason=reason,
                       dia=self.id, node=self.label)

    def _bind_ledger_node(self):
        """The mesh ledger with this node pushed as the current
        decision site, or None — decisions recorded inside compute()
        (exchange strategy, prune verdicts, admission) then attach to
        this node in explain()."""
        led = getattr(self.context.mesh_exec, "decisions", None)
        if led is not None and led.enabled:
            led.push_node(self.id, self.label)
            return led
        return None

    def materialize_plan(self, consume: bool = False):
        """Fused-stage entry: defer this node's program into its sole
        consumer's stitched dispatch when safe (sole consumer, nothing
        cached, fusion on), else materialize normally. Returns a
        FusionPlan (deferred) or Shards."""
        from . import fusion
        mgr = getattr(self.context, "checkpoint", None)
        if mgr is not None and self.state == NEW and (
                mgr.restorable(self) or (mgr.auto and self.parents)):
            # resume: this node's state is on disk — restoring beats
            # deferring into a fused dispatch that would recompute the
            # whole upstream subgraph. Auto-checkpoint mode likewise
            # forces materialization: an epoch can only seal
            # MATERIALIZED shards, so every DOp becomes a durable
            # stage barrier (the documented fusion tradeoff of
            # THRILL_TPU_CKPT_AUTO).
            self._barrier_decision("checkpoint restore/auto-epoch "
                                   "needs materialized shards")
            return self.materialize(consume=consume)
        if (fusion.enabled() and consume and self._shards is None
                and self.state == NEW and self.consume_budget <= 1
                and type(self).compute_plan is not DIABase.compute_plan):
            # the legacy path would negotiate around compute(); plans
            # may fall back to mem-hungry host bodies, so grant here too
            negotiated = self.context.negotiate_mem(self)
            led = self._bind_ledger_node()
            try:
                with self.stage_span():
                    plan = self.compute_plan()
            finally:
                if led is not None:
                    led.pop_node()
                if negotiated:
                    self.context.release_mem(self)
            if plan is not None:
                self.consume_budget = 0
                self.state = FUSED
                log = self.context.logger
                if log.enabled:
                    log.line(event="node_fused", node=self.label,
                             dia_id=self.id,
                             parents=[p.node.id for p in self.parents])
                return plan
            self._barrier_decision("plan ineligible (host storage or "
                                   "untraceable input)")
        elif fusion.enabled() and consume \
                and type(self).compute_plan is not DIABase.compute_plan:
            # statically fusible op that cannot defer THIS pull: name
            # the reason (the explain() barrier kinds). Reaching
            # this branch with consume=True means exactly one of these
            # two defer conditions failed.
            self._barrier_decision(
                "cached result" if self._shards is not None
                or self.state != NEW else "multi-consumer (Keep)")
        return self.materialize(consume=consume)

    def materialize(self, consume: bool = False) -> Shards:
        if self.state in (DISPOSED, FUSED):
            raise RuntimeError(
                f"DIA node {self.label}#{self.id} was consumed/disposed "
                f"(consume budget exhausted); call .Keep() before reusing "
                f"a DIA in more than one operation")
        hbm = self.context.hbm
        if self._shards is None:
            log = self.context.logger
            if log.enabled:
                log.line(event="node_execute_start", node=self.label,
                         dia_id=self.id,
                         parents=[p.node.id for p in self.parents])
            # resume path (api/checkpoint.py): a committed epoch holds
            # this node's shards — rebuild them instead of computing,
            # and the pull recursion never touches the upstream graph
            with self.stage_span():
                mgr = getattr(self.context, "checkpoint", None)
                restored = mgr.try_restore(self) if mgr is not None \
                    else None
                if restored is not None:
                    self._shards = restored
                else:
                    # stage-level HBM admission (mem/pressure.py): before
                    # a new stage computes, bring the cached-results
                    # ledger back under the watermark — the pull-model
                    # analog of the reference's per-stage RAM distribution
                    pres = getattr(self.context, "pressure", None)
                    if pres is not None and pres.enabled:
                        pres.admit_stage(self)
                    # stage memory negotiation: EM operators get a host-RAM
                    # grant split among concurrently computing
                    # max-requesters (nested pulls, e.g. recursive DC3
                    # sorts, shrink the inner grants exactly like the
                    # reference's per-stage split)
                    negotiated = self.context.negotiate_mem(self)
                    led = self._bind_ledger_node()
                    try:
                        self._shards = self.compute()
                    finally:
                        if led is not None:
                            led.pop_node()
                        if negotiated:
                            self.context.release_mem(self)
                    if mgr is not None:
                        # stage-barrier auto-checkpoint (opt-in)
                        mgr.maybe_autosave(self, self._shards)
            self.state = EXECUTED
            if not (consume and self.consume_budget <= 1):
                # a result released by this very pull is never worth
                # spilling a kept sibling for — skip the LRU entirely
                hbm.on_cache(self)
            if log.enabled:
                # never FORCE a counts fetch for the log line: it would
                # reintroduce a per-op host sync, and (multi-controller)
                # a fetch conditional on local logger settings would
                # issue asymmetric collectives across processes
                host_counts = getattr(self._shards, "_counts_host",
                                      self._shards.counts
                                      if isinstance(self._shards,
                                                    HostShards) else None)
                log.line(event="node_execute_done", node=self.label,
                         dia_id=self.id,
                         items=(int(host_counts.sum())
                                if host_counts is not None else None),
                         per_worker=(host_counts.tolist()
                                     if host_counts is not None
                                     else None))
        else:
            # LRU bump; transparently re-uploads a spilled result
            hbm.touch(self)
        result = self._shards
        if consume:
            self.consume_budget -= 1
            if self.consume_budget <= 0:
                self._shards = None
                self.state = DISPOSED
                hbm.on_release(self, None)  # caller now owns `result`
        return result

    def keep(self, n: int = 1) -> None:
        self.consume_budget += n

    def dispose(self) -> None:
        dropped = self._shards
        self._shards = None
        self.state = DISPOSED
        self.context.hbm.on_release(self, dropped)

    def __repr__(self) -> str:
        return f"<{type(self).__name__} {self.label}#{self.id} {self.state}>"
