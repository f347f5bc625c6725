"""Memory-pressure resilience: admission control, OOM classification
and the escalation ladder.

The reference framework's defining robustness property is that
operators degrade to external memory instead of dying when data
outgrows RAM (reference: thrill/data/block_pool.hpp:42 pin/spill
against a hard budget; Sort/Reduce consult ``mem::memory_exceeded``
and fall back to EM algorithms, api/sort.hpp:679). The TPU port's
scarce resource is HBM, and its failure mode is a dispatch dying with
``RESOURCE_EXHAUSTED`` — this module makes that a recoverable,
observable event instead of a job killer.

Four rungs, each louder and slower than the last, none ever wrong:

1. **Admission control** (:meth:`PressureMonitor.admit`, called at the
   ``_CountedJit`` dispatch choke point): a cost model estimates the
   dispatch's output+workspace bytes from its argument shapes (plus a
   learned per-program output size and explicit plan-shape hints from
   api/fusion.py / api/device_exec.py), adds the HbmGovernor's
   live-bytes ledger, and when the sum crosses the watermark fraction
   of the HBM budget, preemptively spills cold cached shards BEFORE
   dispatching (``event=mem_spill``).
2. **OOM-retry** (:func:`recover_dispatch`): a dispatch that still
   dies with device OOM is classified (:func:`is_oom_error`), cold
   cached nodes are spilled, and the dispatch re-runs under the shared
   bounded-backoff budget (``event=oom_retry``) — with donation
   DISARMED on the retry: a donating twin re-dispatches through its
   non-donating base, and carry buffers already consumed by the failed
   dispatch surface as a clean error instead of a deleted-array crash.
3. **Spill-and-split** (api/fusion.py ``FusionPlan`` degraded path):
   when retry is exhausted, a row-local fused segment chain re-plans
   as K row-range sub-dispatches over ``common/partition.py`` bounds
   and reassembles the result (``event=segment_split`` — lineage-level
   like the hinted-join overflow re-run: loud, never wrong data).
4. **Host fallback**: the last rung runs the chain's host-engine form
   (the reference's EM degradation) when even split chunks OOM.

The HBM budget seeds from ``jax.local_devices()[i].memory_stats()``
where the backend reports one (TPU/GPU); ``THRILL_TPU_HBM_LIMIT``
overrides (and is the only way to arm admission on CPU, which reports
no stats — the off path is one attribute read per dispatch).
``THRILL_TPU_OOM_RETRY=0`` disables the whole ladder: every rung
falls away and an OOM surfaces exactly as before this module existed.

Injection sites (CPU-testable without a real OOM):

* ``mem.oom`` — raises :class:`SimulatedOom` at the dispatch choke
  point with a ``RESOURCE_EXHAUSTED`` message, exercising the REAL
  classifier and the real ladder. Declared with kind ``"oom"`` so the
  generic transient dispatch retry (common/retry.py classifies
  injected faults by their declared kind) never absorbs it — the OOM
  ladder owns it end to end.
* ``mem.spill`` — a pressure-triggered spill fails; the ladder
  degrades to dispatch-anyway (over budget beats data loss).
* ``mem.estimate`` — the cost model fails; admission is skipped for
  that dispatch (estimation is advisory, never load-bearing).
"""

from __future__ import annotations

import os
from typing import Any, Optional, Tuple

from ..common import faults
from ..common.retry import RetryPolicy, _env_float, default_policy

OOM_KIND = "oom"


class SimulatedOom(faults.InjectedFault, RuntimeError):
    """Injected device OOM. The message mimics the runtime's
    RESOURCE_EXHAUSTED text so :func:`is_oom_error`'s string matcher —
    the one real XlaRuntimeErrors go through — is what classifies it."""

    def __init__(self, site: str, kind: str = OOM_KIND) -> None:
        faults.InjectedFault.__init__(self, site, kind)
        self.args = (f"RESOURCE_EXHAUSTED: injected out of memory "
                     f"at site '{site}'",)


_F_OOM = faults.declare("mem.oom", kind=OOM_KIND, exc=SimulatedOom)
_F_SPILL = faults.declare("mem.spill")
_F_EST = faults.declare("mem.estimate")

# substrings the accelerator runtimes put in allocation-failure errors
# (PJRT RESOURCE_EXHAUSTED, TFRT/SE allocator messages). Deliberately
# narrow: a generic "OOM" token would false-positive on user errors.
_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "RESOURCE EXHAUSTED",
                "Out of memory", "out of memory",
                "Failed to allocate", "failed to allocate",
                "Attempting to allocate")
# ...and what the TPU compiler puts in its refusal of a kernel that does
# not fit the chip's FAST memory ("RESOURCE_EXHAUSTED: Ran out of memory
# in memory space vmem ... exceeded scoped vmem limit"). No amount of
# HBM spilling makes that program compile, so the ladder must not
# spill and retry it: it surfaces as itself.
_NOT_HBM_MARKERS = ("memory space vmem", "memory space smem",
                    "scoped vmem")


def is_oom_error(exc: BaseException) -> bool:
    """Is this exception a device/allocator out-of-memory failure
    that freeing HBM could cure?"""
    if isinstance(exc, SimulatedOom):
        return True
    if isinstance(exc, faults.InjectedFault):
        return False            # other injections simulate other faults
    if isinstance(exc, MemoryError):
        return True
    if not isinstance(exc, (RuntimeError, ValueError, OSError)):
        return False            # XlaRuntimeError is a RuntimeError
    msg = str(exc)
    if any(m in msg for m in _NOT_HBM_MARKERS):
        return False
    return any(m in msg for m in _OOM_MARKERS)


def retry_enabled() -> bool:
    """THRILL_TPU_OOM_RETRY=0 disables the whole escalation ladder."""
    return os.environ.get("THRILL_TPU_OOM_RETRY", "1") not in (
        "0", "off", "false")


def split_k(cap: int) -> int:
    """THRILL_TPU_SPLIT_K clamped to [2, cap]: the rung-3 row-range
    sub-dispatch count. ONE implementation shared by the reactive
    ladder (api/fusion.py _execute_degraded) and the adaptive
    planner's proactive split (api/planner.py), so the two paths
    always produce the same sub-plan."""
    try:
        k = int(os.environ.get("THRILL_TPU_SPLIT_K", "4") or 4)
    except ValueError:
        k = 4
    return max(2, min(k, cap))


def detect_hbm_budget() -> int:
    """Per-device HBM budget in bytes; 0 = unknown (admission off).

    ``THRILL_TPU_HBM_LIMIT`` overrides; otherwise the smallest
    ``bytes_limit`` any local device reports (TPU/GPU backends; CPU
    reports nothing, so admission needs the env var there)."""
    env = os.environ.get("THRILL_TPU_HBM_LIMIT")
    if env:
        from ..common.config import parse_si_iec_units
        try:
            return parse_si_iec_units(env)
        except (ValueError, TypeError):
            import sys
            print(f"thrill_tpu: bad THRILL_TPU_HBM_LIMIT={env!r}; "
                  f"ignoring", file=sys.stderr)
    import jax
    limits = []
    try:
        for d in jax.local_devices():
            try:
                ms = d.memory_stats()
            except Exception:
                ms = None
            if ms and ms.get("bytes_limit"):
                limits.append(int(ms["bytes_limit"]))
    except Exception:
        return 0
    return min(limits) if limits else 0


class PressureMonitor:
    """Per-mesh memory-pressure state: the cost model, the watermark,
    and the ladder's counters. Owned by the Context (one per
    HbmGovernor) and attached as ``mesh_exec.pressure`` so the
    dispatch choke point reaches it in one attribute read."""

    def __init__(self, mesh_exec, governor=None,
                 budget: Optional[int] = None) -> None:
        self.mex = mesh_exec
        self.governor = governor
        self.budget = detect_hbm_budget() if budget is None else budget
        self.watermark = _env_float("THRILL_TPU_HBM_WATERMARK", 0.85)
        if not (0.0 < self.watermark <= 1.0):
            self.watermark = 0.85
        # admission runs only with BOTH a budget and a live-bytes
        # ledger; plain bool so the per-dispatch gate is two attribute
        # reads on the off path
        self.enabled = bool(self.budget > 0 and governor is not None)
        self.est_factor = _env_float("THRILL_TPU_MEM_EST_FACTOR", 2.0)
        # escalation-ladder counters (ctx.overall_stats surfaces them)
        self.oom_retries = 0
        self.segment_splits = 0
        self.host_fallbacks = 0
        self.admission_spills = 0
        self.spilled_bytes = 0
        self.high_watermark = 0     # max (ledger + estimate) observed
        # one-slot output-bytes hint for the NEXT dispatch, set by the
        # planners (api/fusion.py, api/device_exec.py) that know the
        # plan's output shapes before the program runs
        self._out_hint: Optional[int] = None

    # -- cost model -----------------------------------------------------
    def hint_output_bytes(self, nbytes: int) -> None:
        self._out_hint = int(nbytes)

    def estimate_call_bytes(self, fn, args) -> int:
        """Output+workspace estimate for one dispatch: argument bytes
        plus the best available output prediction — an explicit plan
        hint, the program's learned output size from a previous run,
        or ``est_factor`` times the inputs as the cold-start guess."""
        if faults.REGISTRY.active():
            faults.check(_F_EST)
        import jax
        in_bytes = 0
        for a in args:
            for l in jax.tree.leaves(a):
                in_bytes += int(getattr(l, "nbytes", 0) or 0)
        hint = self._out_hint
        self._out_hint = None
        if hint is None:
            hint = getattr(fn, "_out_bytes", None)
        if hint is not None:
            est = in_bytes + int(hint)
        else:
            est = int(in_bytes * self.est_factor)
        if getattr(fn, "_out_bytes", None) is None:
            # first (cold or hinted) estimate for this program: stash
            # it for the decision ledger's predicted-vs-actual join at
            # the dispatch choke point once the real output bytes are
            # measured (parallel/mesh.py; common/decisions.py)
            try:
                fn._adm_est = (est, in_bytes)
            except AttributeError:
                pass               # bare stubs refusing attributes
        return est

    def inadmissible(self, est_bytes: int) -> bool:
        """True when ``est_bytes`` cannot fit under the watermark at
        ANY spill level — the estimate exceeds the watermark fraction
        of the whole budget, so no amount of cold-shard eviction can
        admit it. The adaptive planner (api/planner.py) uses this as
        the cost model's HBM term: such a plan is chosen around
        (proactive fusion split) instead of dispatched into a certain
        rung-2/3 escalation."""
        return self.enabled and est_bytes > self.budget * self.watermark

    # -- rung 1: admission ----------------------------------------------
    def admit(self, fn, args) -> None:
        """Pre-dispatch admission: spill cold cached shards until the
        ledger plus this dispatch's estimate fits under the watermark.
        Estimation/spill failures degrade to dispatch-anyway — rung 2
        still guards the actual OOM."""
        try:
            est = self.estimate_call_bytes(fn, args)
        except Exception as e:
            faults.note("recovery", what="mem.estimate_skipped",
                        error=repr(e)[:200])
            return
        gov = self.governor
        live = gov.mem.total
        if live + est > self.high_watermark:
            self.high_watermark = live + est
        limit = int(self.budget * self.watermark)
        if live + est <= limit:
            return
        # never spill the dispatch's OWN input nodes: their device
        # arrays stay alive through `args` for the whole dispatch, so
        # evicting them decrements the ledger without freeing any HBM
        # (and buys a pointless spill+restore round trip)
        import jax
        live_bufs = {id(l) for a in args for l in jax.tree.leaves(a)}
        try:
            freed = self.spill_cold(need=live + est - limit,
                                    exclude_buffers=live_bufs)
        except Exception as e:
            faults.note("recovery", what="mem.pressure_spill_skipped",
                        error=repr(e)[:200])
            return
        if freed:
            faults.note("mem_spill", freed=freed, estimate=est,
                        live=live, budget=self.budget)
            self._trace_rung("admission_spill", freed=freed)

    def admit_stage(self, node) -> None:
        """Stage-level admission (api/dia_base.py): before a node's
        compute, bring the cached-results ledger back under the
        watermark — the pull-model analog of the reference's per-stage
        RAM distribution clearing room before a stage runs."""
        if not self.enabled:
            return
        gov = self.governor
        live = gov.mem.total
        limit = int(self.budget * self.watermark)
        if live > self.high_watermark:
            self.high_watermark = live
        if live <= limit:
            return
        try:
            freed = self.spill_cold(need=live - limit,
                                    exclude=getattr(node, "id", None))
        except Exception as e:
            faults.note("recovery", what="mem.pressure_spill_skipped",
                        error=repr(e)[:200])
            return
        if freed:
            faults.note("mem_spill", freed=freed, live=live,
                        budget=self.budget, node=node.label)
            self._trace_rung("admission_spill", freed=freed)

    def spill_cold(self, need: Optional[int] = None,
                   exclude: Optional[int] = None,
                   exclude_buffers: Optional[set] = None,
                   admission: bool = True) -> int:
        """Unconditionally spill LRU-coldest cached nodes (restorable
        state only — a spilled node's next pull re-uploads) until
        ``need`` bytes are freed or nothing cold remains. Nodes whose
        shard buffers appear in ``exclude_buffers`` (the in-flight
        dispatch's argument leaves) are skipped — evicting them cannot
        free HBM while the dispatch holds the arrays.
        ``admission=False`` (the OOM-retry rung) keeps the freed bytes
        in ``pressure_spilled_bytes`` but out of ``admission_spills``,
        so the stats attribute each spill to the rung that caused it.
        Returns the bytes actually freed."""
        import jax
        gov = self.governor
        if gov is None:
            return 0
        freed = 0
        for nid in list(gov._lru.keys()):
            if nid == exclude:
                continue
            node = gov._lru.get(nid)
            if node is None:
                continue            # a nested pass already handled it
            if exclude_buffers:
                shards = getattr(node, "_shards", None)
                tree = getattr(shards, "tree", None)
                if tree is not None and any(
                        id(l) in exclude_buffers
                        for l in jax.tree.leaves(tree)):
                    continue
            faults.check(_F_SPILL, node=getattr(node, "label", "?"))
            before = gov.mem.total
            gov.spill(node)
            freed += max(before - gov.mem.total, 0)
            if need is not None and freed >= need:
                break
        if freed:
            if admission:
                self.admission_spills += 1
            self.spilled_bytes += freed
            # the eviction choice in the decision ledger: which policy
            # (LRU-cold) ran, what it was asked to free, what it freed
            # — ctx.explain()'s I/O coverage alongside io_prefetch
            from ..common.decisions import record_of, resolve_of
            rec = record_of(self.mex, "io_evict", "mem.pressure",
                            "spill-lru-cold",
                            predicted=need if need else None,
                            reason="admission watermark" if admission
                            else "oom-retry ladder")
            resolve_of(self.mex, rec, freed)
        return freed

    def _trace_rung(self, rung: str, **attrs) -> None:
        """Ladder-rung marker on the "mem" trace lane (common/trace.py)
        — a Perfetto timeline shows WHEN each escalation fired relative
        to the dispatch/exchange spans around it."""
        from ..common.trace import instant_of
        instant_of(getattr(self.mex, "tracer", None), "mem", rung,
                   **attrs)

    def stats(self) -> dict:
        return {
            "hbm_high_watermark": self.high_watermark,
            "oom_retries": self.oom_retries,
            "segment_splits": self.segment_splits,
            "host_fallbacks": self.host_fallbacks,
            "admission_spills": self.admission_spills,
            "pressure_spilled_bytes": self.spilled_bytes,
        }

def _monitor_for(mex) -> PressureMonitor:
    """The mesh's monitor; a bare mesh (no Context yet) gets a
    ledger-less one so the OOM ladder can still count and retry."""
    pres = getattr(mex, "pressure", None)
    if pres is None:
        pres = PressureMonitor(mex)
        mex.pressure = pres
    return pres


# ----------------------------------------------------------------------
# rung 2: OOM-retry at the dispatch choke point
# ----------------------------------------------------------------------

class _OomRetryPolicy(RetryPolicy):
    """The shared policy with OOM-specific classification: device OOM
    is the transient class this rung retries (the base classify would
    call an XlaRuntimeError permanent and a SimulatedOom by its 'oom'
    kind); everything else surfaces on first raise."""

    def classify(self, exc: BaseException) -> str:
        return faults.TRANSIENT if is_oom_error(exc) else faults.PERMANENT


def recover_dispatch(fn, args, kwargs, exc: BaseException):
    """Handle a device OOM raised by ``fn``'s jitted dispatch: spill
    cold cached nodes and re-dispatch under the shared bounded-backoff
    policy (common/retry.py — same budget/backoff env knobs as every
    other retry layer), donation disarmed. Re-raises the last OOM when
    the budget is exhausted (the caller — the fusion planner — owns
    the next rung). ``fn`` is the ``_CountedJit`` whose dispatch
    failed; non-OOM errors never reach here."""
    mex = fn._mex
    if getattr(mex, "num_processes", 1) > 1:
        # per-process degradation on a multi-controller mesh would
        # desynchronize the collective schedule: this process would
        # spill and re-enter the SPMD program alone while a peer whose
        # dispatch failed differently (or succeeded) never does —
        # turning a clean OOM abort into a watchdog-timeout hang. Same
        # reasoning as the governor's multi-process spill guard and
        # the fusion planner's split/host-rung guard: re-raise.
        raise exc
    pres = _monitor_for(mex)

    # donation disarm: a donating twin must not re-donate buffers the
    # failed dispatch may already have consumed — retry through the
    # non-donating base program, and if donation DID consume an input,
    # surface a clean error instead of a deleted-array crash.
    base = getattr(fn, "_donate_base", None)
    target = fn._jitted if base is None else base._jitted
    if base is not None:
        import jax
        for a in args:
            for l in jax.tree.leaves(a):
                if isinstance(l, jax.Array) and l.is_deleted():
                    raise RuntimeError(
                        "device OOM after a donated input buffer was "
                        "consumed by the failed dispatch; cannot "
                        "retry in place (re-run with "
                        "THRILL_TPU_LOOP_DONATE=0)") from exc

    shared = default_policy()
    # the failed dispatch already consumed one attempt of the shared
    # budget, so this rung gets max_attempts-1 re-dispatches. run()
    # always makes at least one attempt, so "no retries left" (a
    # 1-attempt budget, or the THRILL_TPU_RETRY=0 kill switch run()
    # would otherwise clamp to one attempt) must re-raise HERE
    if shared.max_attempts <= 1 \
            or os.environ.get("THRILL_TPU_RETRY", "1") == "0":
        raise exc
    policy = _OomRetryPolicy(
        max_attempts=shared.max_attempts - 1,
        base_delay_s=shared.base_delay_s,
        max_delay_s=shared.max_delay_s)
    state = {"last": exc}

    def attempt():
        try:
            freed = pres.spill_cold(admission=False)
        except Exception as e:
            faults.note("recovery", what="mem.pressure_spill_skipped",
                        error=repr(e)[:200])
            freed = 0
        pres.oom_retries += 1
        faults.note("oom_retry", freed=freed,
                    donating=base is not None,
                    error=repr(state["last"])[:200])
        pres._trace_rung("oom_retry", freed=freed)
        try:
            if faults.REGISTRY.active():
                # the injection site rides every RETRY too, so a
                # multi-fire arming can exhaust this rung on demand
                # and hand the failure to the split rung
                faults.check(_F_OOM, retry=True)
            out = target(*args, **kwargs)
        except Exception as e:
            state["last"] = e
            raise
        faults.note("recovery", what="mem.oom", _quiet=True)
        return out

    return policy.run(attempt, what="mem.oom_retry")
