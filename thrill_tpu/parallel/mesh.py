"""Mesh execution: the device-side worker model.

The reference's worker model is host processes x worker threads connected
by a TCP/MPI full mesh (reference: thrill/api/context.hpp:90-243). The
TPU-native equivalent is a ``jax.sharding.Mesh`` over a 1-D ``'w'``
(worker) axis: one logical Thrill worker per device. Per-worker state is
the device shard of globally-sharded arrays; communication is XLA
collectives over ICI/DCN inside jitted SPMD programs built with
``jax.shard_map``.

Multi-host scaling: initialize ``jax.distributed`` and pass the global
device list — the same jitted programs then span hosts, with XLA routing
collectives over ICI within a slice and DCN across slices. Nothing in the
operator layer changes, which is the point of designing single-controller
SPMD from the start.
"""

from __future__ import annotations

import functools
import threading
import time
import weakref
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..common import faults
from ..common.retry import default_policy
from ..common.trace import DeviceWatcher, span_of
from ..mem import pressure as _pressure
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


AXIS = "w"

# device dispatch is PURE (jitted functional program over immutable
# buffers), so a transient runtime/transport fault — a preempted PJRT
# stream — retries safely under the shared backoff policy before
# surfacing
_F_DISPATCH = faults.declare("api.mesh.dispatch")

# Trace-time back-channel: while a program dispatches (including its
# FIRST call, when jax traces the python builder), the owning mesh and
# the _CountedJit being run are visible here. Plan choke points that
# live INSIDE traced builders (core/device_sort.py's engine choice)
# use this to reach the decision ledger without threading a mex handle
# through every functional signature; the compile listener names its
# span by the program.
_TL = threading.local()


def current_mex() -> Optional["MeshExec"]:
    """The MeshExec whose program is currently dispatching (or being
    traced) on this thread; None outside a dispatch."""
    return getattr(_TL, "mex", None)


def current_program() -> Optional["_CountedJit"]:
    """The _CountedJit currently dispatching on this thread."""
    return getattr(_TL, "prog", None)


def note(kind: str, k: int = 1) -> None:
    """Trace time: the program being traced does ``k`` of ``kind``, a
    counter of ``overall_stats()`` that :meth:`MeshExec.add_noted`
    knows: ``sort_keys_reused`` (sorted key-word arrays taken from a
    sort's output where they would have been gathered by the
    permutation, core/device_sort.py ``sort_words``) or
    ``send_hists_by_compare`` (send histograms counted by comparison,
    data/exchange.py ``send_counts``). Kept on the traced program
    (:func:`_noting`); outside one, nothing is noted."""
    notes = getattr(_TL, "notes", None)
    if notes is not None:
        notes[kind] = notes.get(kind, 0) + k


def _noting(fn: Callable) -> Callable:
    """``fn`` keeping on itself, as ``noted``, what its trace noted.
    jax traces a program once and shares that trace between ``lower``,
    the first call and a donating twin, whichever comes first, so what
    a trace notes lives on the traced function; a program traced inside
    another (a call in a whole-loop body) notes on its own."""
    @functools.wraps(fn)
    def traced(*args, **kwargs):
        prev = getattr(_TL, "notes", None)
        _TL.notes = notes = {}
        try:
            return fn(*args, **kwargs)
        finally:
            _TL.notes = prev
            traced.noted = notes

    traced.noted = {}
    return traced


_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_compile_listener_on = False


def _on_jax_duration(event: str, duration_secs: float, **_kw) -> None:
    """Process-wide ``jax.monitoring`` listener: a backend compile (or a
    load from the compile cache, which JAX times under the same event
    and announces just before it) on a dispatching thread becomes a
    ``compile`` span of that mesh's Tracer, named by the dispatching
    program and parented to its open ``dispatch`` span."""
    if event == _CACHE_LOAD_EVENT:
        _TL.cache_load = True
        return
    if event != _BACKEND_COMPILE_EVENT:
        return
    cache_load = getattr(_TL, "cache_load", False)
    _TL.cache_load = False
    mex = current_mex()
    if mex is None:
        return
    mex.stats_compiles += 1
    mex.stats_compile_s += duration_secs
    tr = mex.tracer
    if tr is not None and tr.enabled:
        now = time.perf_counter()
        tr.emit_span("compile", current_program()._label(),
                     now - duration_secs, now, parent=tr.current_id(),
                     seconds=duration_secs, jax_event="backend_compile",
                     cache_load=cache_load)


def _listen_for_compiles() -> None:
    global _compile_listener_on
    if not _compile_listener_on:
        _compile_listener_on = True
        jax.monitoring.register_event_duration_secs_listener(
            _on_jax_duration)


def _named(fn: Callable, name: Optional[str]) -> Callable:
    """``fn`` under ``name``: ``jax.jit`` calls the module
    ``jit_<__name__>``, which is what the device plane's ``XLA
    Modules`` line of a profile shows."""
    if not name or getattr(fn, "__name__", None) == name:
        return fn

    @functools.wraps(fn)
    def named(*args, **kwargs):
        return fn(*args, **kwargs)

    named.__name__ = named.__qualname__ = name
    return named


class _CountedJit:
    """Dispatch-counting proxy around a ``jax.jit`` callable.

    Every attribute other than ``__call__`` delegates to the jitted
    function (``.lower``, ``.trace``, ``.clone``, cost analysis...), so
    AOT/introspection callers see the real jit object — only calls gain
    the dispatch counter and the fault-injected retry.

    ``raw`` keeps the pre-jit callable (the shard_map program) so the
    loop-replay layer (api/loop.py) can build DONATING twins
    (``jax.jit(raw, donate_argnums=...)``) and trace the program into a
    whole-loop ``lax.fori_loop`` body."""

    def __init__(self, mex: "MeshExec", jitted: Callable,
                 raw: Optional[Callable] = None,
                 label: Optional[str] = None) -> None:
        self._mex = mex
        self._jitted = jitted
        self.raw = raw
        # the MeshExec.cached key this program was built under (stamped
        # by cached()); the loop layer keys derived whole-loop programs
        # on it so equal tapes share ONE compiled fori_loop
        self.cache_key: Optional[Tuple] = None
        self._donating: Dict[Tuple[int, ...], Callable] = {}
        # memory-pressure cost model (mem/pressure.py): the program's
        # measured output bytes, learned on the first successful call;
        # the donating-twin back-pointer lets the OOM ladder re-dispatch
        # with donation disarmed
        self._out_bytes: Optional[int] = None
        # (estimate, input_bytes) stashed by admission for the decision
        # ledger's predicted-vs-actual join on the first measured call
        # (common/decisions.py; plain attr — __getattr__ delegates
        # unknown names to the jitted function, so it must exist here)
        self._adm_est: Optional[Tuple[int, int]] = None
        self._donate_base: Optional["_CountedJit"] = None
        # ReduceToIndex index plans that one run of this program
        # computes in place (api/fusion.py note_index_plans): every
        # dispatch adds them to ``r2i_index_plans`` and says so on its
        # ``dispatch`` span; of them the dense ones, which also go to
        # ``r2i_dense_plans``
        self.index_plans = 0
        self.dense_plans = 0
        # the name the jitted callable carries (module ``jit_<label>``
        # on the device plane) and every host span of this program
        self._trace_label: Optional[str] = label
        functools.update_wrapper(self, jitted, updated=())

    @property
    def noted(self) -> Dict[str, int]:
        """What one run of this program does of each counter its trace
        noted (:func:`note`): every dispatch adds it
        (:meth:`MeshExec.add_noted`); empty until the program has been
        traced."""
        base = self._donate_base or self
        return getattr(base.raw, "noted", {})

    def _label(self) -> str:
        return self._trace_label \
            or getattr(self._jitted, "__name__", None) or "jit"

    def __call__(self, *args, **kwargs):
        # tracing fast path (the pinned overhead contract,
        # tests/common/test_trace.py): THRILL_TPU_TRACE=0 costs one
        # attribute read plus one predicate — no span objects, no
        # context managers, nothing else
        tr = self._mex.tracer
        if tr is None or not tr.enabled:
            return self._dispatch(args, kwargs)
        with tr.span("dispatch", self._label(),
                     index_plans=self.index_plans) as sp:
            out = self._dispatch(args, kwargs)
            # the final outputs (after an OOM-ladder retry), for the
            # ``device`` record of when they are ready
            self._mex._watch().device(sp, out, (args, kwargs))
            return out

    def _dispatch(self, args, kwargs):
        mex = self._mex
        mex.stats_dispatches += 1
        mex.stats_r2i_index_plans += self.index_plans
        mex.stats_r2i_dense_plans += self.dense_plans
        pres = mex.pressure
        if pres is not None and pres.enabled:
            # rung 1, admission control: estimate this dispatch's
            # output+workspace bytes and pre-spill cold cached shards
            # when the governor ledger says HBM is near the watermark
            pres.admit(self, args)
        prev_mex = getattr(_TL, "mex", None)
        prev_prog = getattr(_TL, "prog", None)
        _TL.mex, _TL.prog = mex, self
        t0 = time.perf_counter()
        try:
            try:
                if not faults.REGISTRY.active():
                    # disarmed hot path: dispatch-per-iteration is the
                    # budgeted cost in this codebase — no policy
                    # construction, no env reads beyond active()'s one
                    out = self._jitted(*args, **kwargs)
                else:
                    def dispatch():
                        faults.check(_F_DISPATCH)
                        faults.check(_pressure._F_OOM)
                        return self._jitted(*args, **kwargs)

                    out = default_policy().run(dispatch,
                                               what="mesh.dispatch")
            except Exception as e:
                # rung 2, OOM-retry: device RESOURCE_EXHAUSTED spills
                # the LRU cache and re-dispatches (donation disarmed)
                # under the shared backoff budget; anything else — and
                # every error with the ladder disabled — re-raises
                # unchanged
                if not (_pressure.retry_enabled()
                        and _pressure.is_oom_error(e)):
                    raise
                out = _pressure.recover_dispatch(self, args, kwargs, e)
        finally:
            _TL.mex, _TL.prog = prev_mex, prev_prog
        # Dispatch-latency spine (ROADMAP planner edge (b)): the
        # running MIN over calls converges on the pure launch overhead
        # (trace/compile calls are strictly slower, so min excludes
        # them); data/exchange.py calibrates bytes_eq from it once
        # enough samples accumulate. Two perf_counter reads per
        # dispatch — no allocation, no env reads.
        dt = time.perf_counter() - t0
        # after the call: a program's first call is its trace
        mex.add_noted(self.noted)
        if dt < mex._disp_lat_min:
            mex._disp_lat_min = dt
        mex._disp_lat_n += 1
        if pres is not None and pres.enabled and self._out_bytes is None:
            self._out_bytes = sum(
                int(getattr(l, "nbytes", 0) or 0)
                for l in jax.tree.leaves(out))
            # decision-ledger join at the dispatch choke point: the
            # admission cost model predicted this program's bytes
            # before its first run; the measured output is the truth.
            # THRILL_TPU_DECISIONS=0 pays exactly one attribute read
            # plus one predicate here and allocates nothing (pinned by
            # tests/common/test_decisions.py via RECORDS_CREATED).
            led = mex.decisions
            if led is not None and led.enabled \
                    and self._adm_est is not None:
                est, in_bytes = self._adm_est
                self._adm_est = None
                rec = led.record(
                    "admission", site="jit:" + self._label(),
                    chosen="admit", predicted=est,
                    reason="first estimate for this program",
                    in_bytes=in_bytes)
                led.resolve(rec, in_bytes + self._out_bytes)
        rec = mex.loop_recorder
        if rec is not None:
            rec.on_call(self, args, kwargs, out)
        return out

    def donating(self, donate_argnums: Tuple[int, ...]) -> Callable:
        """A twin executable that donates the given argument buffers
        (loop-carried HBM reuse on replayed dispatches). Compiled once
        per donation signature; requires ``raw``."""
        fn = self._donating.get(donate_argnums)
        if fn is None:
            if self.raw is None:
                raise ValueError("no raw program retained; cannot "
                                 "build a donating twin")
            fn = _CountedJit(self._mex,
                             jax.jit(self.raw,
                                     donate_argnums=donate_argnums),
                             label=self._label())
            # the OOM ladder (mem/pressure.py) retries a failed
            # donating dispatch through THIS base so the retry never
            # re-donates buffers the failed attempt may have consumed
            fn._donate_base = self
            fn.index_plans = self.index_plans
            fn.dense_plans = self.dense_plans
            self._donating[donate_argnums] = fn
        return fn

    def __getattr__(self, name):
        return getattr(self._jitted, name)


class MeshExec:
    """Owns the worker mesh and caches compiled SPMD programs."""

    def __init__(self, devices: Optional[Sequence[Any]] = None,
                 num_workers: int = 0, backend: Optional[str] = None) -> None:
        if devices is None:
            devices = jax.devices(backend) if backend else jax.devices()
            if num_workers:
                if num_workers > len(devices):
                    raise ValueError(
                        f"requested {num_workers} workers but only "
                        f"{len(devices)} devices available")
                devices = devices[:num_workers]
        self.devices = list(devices)
        self.num_workers = len(self.devices)
        self.mesh = Mesh(np.asarray(self.devices), (AXIS,))
        self._cache: Dict[Any, Callable] = {}
        # cumulative data-plane traffic (cross-worker items/bytes)
        self.stats_exchanges = 0
        self.stats_items_moved = 0
        self.stats_bytes_moved = 0
        # padded rows allocated by exchange plans (skew diagnostics)
        self.stats_padded_rows = 0
        # overlapped-exchange data plane (data/exchange.py): exchanges
        # dispatched optimistically on a cached capacity plan (no
        # mid-shuffle host sync), capacity-plan cache hits/misses, and
        # the bytes that actually cross the fabric/wire — padded rows
        # on the device plane, serialized frames on the host plane
        # (the baseline for ROADMAP's shrink-the-wire item)
        self.stats_exchanges_overlapped = 0
        self.stats_cap_cache_hits = 0
        self.stats_cap_cache_misses = 0
        self.stats_bytes_wire_device = 0
        self.stats_bytes_wire_host = 0
        # shrink-the-wire layer: what full-width device rows would have
        # shipped (actual is bytes_wire_device, narrowed), and host
        # frame bytes saved by the column codec (net/wire.py) — the
        # two halves of wire_compress_ratio in overall_stats
        self.stats_bytes_wire_device_raw = 0
        self.stats_bytes_wire_host_saved = 0
        # chunked-exchange accumulator donation (data/exchange.py
        # _dispatch_chunked): dispatches that actually armed
        # donate_argnums on the chunk accumulator — 0 on CPU where
        # aliasing is never real, >0 on TPU where the HBM reuse pays
        self.stats_xchg_donated = 0
        # send blocks the dispatched exchange programs cut out of their
        # dest-sorted rows as slices (data/exchange.py send_slice): W x
        # shipped leaves per dense program (a chunk, Sort's fused
        # exchange-merge), one x leaves per 1-factor round
        self.stats_xchg_send_slices = 0
        # rows that entered an exchange (the send matrix's total) and
        # those whose destination was their own worker (its trace),
        # added with the traffic (data/exchange.py account_traffic);
        # exchanges whose destination program filled ReduceByKey's
        # duplicate-detection presence registers (api/ops/reduce.py)
        self.stats_xchg_rows_in = 0
        self.stats_xchg_rows_local = 0
        self.stats_dup_detect_exchanges = 0
        # per-exchange-site plan kind ('dense' = optimistic-eligible,
        # 'sync' = the site needs the host plan step every time); the
        # capacity values themselves live in _sticky_caps
        self._xchg_plan: Dict[Any, str] = {}
        # device-program dispatch / host<->device transfer counters.
        # Every dispatch and every sync pays a fixed launch cost, so
        # DISPATCH COUNT — not FLOPs or bytes — governs small-to-medium
        # pipelines; these counters make it observable and testable
        # (tests/api/test_dispatch_budget.py)
        self.stats_dispatches = 0
        self.stats_uploads = 0
        self.stats_fetches = 0
        self.stats_upload_cache_hits = 0
        # host-phase seconds and bytes, added where the phase's span
        # ends (plain adds that run with tracing off too): uploads end
        # when jax.device_put RETURNS, not when the bytes are on the
        # device; sync_wait is the thread blocked on the device before
        # a fetch's copy; compiles are backend compiles or loads from
        # the compile cache under a dispatch
        self.stats_upload_s = 0.0
        self.stats_upload_bytes = 0
        # bytes the staging of host arrays wrote on the host before
        # their put (data/shards.py): 0 where the caller's arrays went
        # up as views of themselves
        self.stats_stage_copy_bytes = 0
        self.stats_fetch_s = 0.0
        self.stats_fetch_bytes = 0
        self.stats_sync_wait_s = 0.0
        self.stats_compiles = 0
        self.stats_compile_s = 0.0
        _listen_for_compiles()
        # program stitching (api/fusion.py): dispatches launched by the
        # fused runner, total DOp segments they carried, and per-stage
        # composition (tuple of op labels -> launch count) — the
        # dispatch budget's observability surface
        self.stats_fused_dispatches = 0
        self.stats_fused_ops = 0
        self.fused_stage_counts: Dict[Tuple[str, ...], int] = {}
        # iteration execution layer (api/loop.py): LoopPlan captures,
        # tape replays (iterations that paid ZERO graph construction /
        # planning), whole-loop fori_loop dispatches, loud replay
        # fallbacks to full re-planning, and HBM bytes donated back to
        # XLA on replayed dispatches
        self.stats_loop_plan_builds = 0
        self.stats_loop_plan_rebinds = 0
        # index plans of ReduceToIndex (api/ops/reduce.py) computed by
        # the programs dispatched, counted where those are dispatched
        # (api/fusion.py, api/loop.py): one per fold of 8-byte sums; in
        # a whole-loop program one per iteration where the index
        # changes with the carry, and one per dispatch where it does not
        self.stats_r2i_index_plans = 0
        # of them the plans of a dense fold (first arrivals by masked
        # min; core/segmented.py dense_fold_plan), counted alike
        self.stats_r2i_dense_plans = 0
        # sorted key-word arrays the dispatched programs took from their
        # sort's output instead of gathering them by the permutation
        # (core/device_sort.py sort_words), counted where those are
        # dispatched (_CountedJit._dispatch, api/loop.py run_fori)
        self.stats_sort_keys_reused = 0
        # send histograms (data/exchange.py send_counts) the dispatched
        # programs count by comparison, not by a scatter-add, counted
        # alike
        self.stats_send_hists_by_compare = 0
        # root ``stage`` spans opened (api/dia_base.py stage_span): one
        # per pull an action or a loop starts; 0 with the tracer off
        self.stats_pulls = 0
        self.stats_loop_replays = 0
        self.stats_loop_fori_iters = 0
        self.stats_loop_fallbacks = 0
        self.stats_loop_donated_bytes = 0
        # active tape recorder (None = zero-overhead fast path); set by
        # api/loop.py around a capture iteration's body run
        self.loop_recorder = None
        # memory-pressure monitor (mem/pressure.py), attached by the
        # Context once the HbmGovernor exists; None = the dispatch
        # choke point pays one attribute read and no admission runs
        self.pressure = None
        # tracing spine (common/trace.py), attached by the Context;
        # None (bare mesh) or tracer.enabled False (THRILL_TPU_TRACE=0)
        # = the dispatch choke point pays one attribute read plus one
        # predicate and allocates nothing
        self.tracer = None
        # when uploads and programs are done on the device (_watch)
        self._watcher: Optional[DeviceWatcher] = None
        # decision ledger (common/decisions.py), attached by the
        # Context; same off-path contract as the tracer — None or
        # THRILL_TPU_DECISIONS=0 means every plan-choice choke point
        # pays one attribute read plus one predicate
        self.decisions = None
        # adaptive cost-based planner (api/planner.py), attached by
        # the Context; None or THRILL_TPU_PLANNER=0 means every plan
        # choice takes its legacy per-site heuristic branch exactly
        self.planner = None
        # per-Iterate reports (phase timings, replay hit rate) for
        # tools/loop_report.py
        self.loop_reports: list = []
        # tapes kept for the next Iterate call of the same loop
        # (api/loop.py _share_token -> LoopPlan)
        self.loop_plans: Dict[Tuple, Any] = {}
        self._put_small_cache: Dict[Any, jax.Array] = {}
        # deferred device-side validations (e.g. InnerJoin
        # out_size_hint overflow): ops that skip a blocking host sync
        # enqueue a check here; every host fetch drains the queue, so
        # no pipeline can reach its action egress past a failed check
        self._pending_checks: list = []
        # lineage recoveries: hinted joins transparently re-run without
        # their hint after a detected overflow (api/ops/join.py)
        self.stats_join_overflow_retries = 0
        # service plane (service/): data-driven host plan constructions
        # — synced exchange capacity plans (data/exchange.py
        # _exchange_planned) and pre-shuffle cost-model evaluations
        # (core/preshuffle.py) — versus plan-store seeds consumed
        # instead. A warm restart of a known pipeline against a
        # populated store runs with stats_plan_builds == 0 (the
        # acceptance counter of the persistent plan store; the Context
        # owns the store handle, service/plan_store.py)
        self.stats_plan_builds = 0
        self.stats_plan_store_hits = 0
        # ICI-vs-DCN split of bytes_moved (multi-slice meshes; equal to
        # bytes_moved/0 on a single slice)
        self.stats_bytes_ici = 0
        self.stats_bytes_dcn = 0
        # exchange implementation ('dense' | 'onefactor' | 'ragged');
        # Context sets it from Config.exchange, THRILL_TPU_EXCHANGE
        # env overrides ('dense' auto-switches to 1-factor under skew).
        # The env override is read ONCE here: resolve_mode() used to
        # pay an os.environ lookup on every exchange plan step — set
        # the variable before constructing the mesh
        self.exchange_mode = "dense"
        import os as _os
        self._env_exchange = _os.environ.get("THRILL_TPU_EXCHANGE")
        # Pallas kernel tier knob, resolved ONCE here (same contract
        # as _env_exchange above): core/pallas_kernels.pallas_enabled()
        # used to pay an os.environ lookup per call, and it runs inside
        # traced builders — set THRILL_TPU_PALLAS before constructing
        # the mesh
        self._env_pallas = _os.environ.get("THRILL_TPU_PALLAS")
        # dispatch-latency spine for the planner's live bytes_eq
        # calibration (edge (b)): running min + sample count, updated
        # at the _CountedJit choke point
        self._disp_lat_min = float("inf")
        self._disp_lat_n = 0
        # slice topology: collectives between same-slice workers ride
        # ICI, cross-slice DCN. Detected from the device objects'
        # slice_index (real multi-slice pods); THRILL_TPU_SLICES=k
        # overrides with k contiguous blocks (virtual-mesh testing).
        self.slice_id = self._detect_slices()
        self.num_slices = int(self.slice_id.max()) + 1 \
            if len(self.slice_id) else 1
        # controller topology: which PROCESS owns each worker's device.
        # The host-storage data plane (data/multiplexer.py) keeps each
        # process holding only its own workers' items and ships the
        # rest over the host control plane (the reference's Multiplexer
        # moving serialized Blocks between hosts,
        # thrill/data/multiplexer.cpp:282-440).
        self.worker_process = np.array(
            [getattr(d, "process_index", 0) for d in self.devices],
            dtype=np.int64)
        self.process_index = int(jax.process_index())
        self.num_processes = len(set(self.worker_process.tolist())) or 1
        # host-plane collectives between processes (FlowControlChannel
        # over the authenticated TCP group); Context wires it so the
        # host-storage layer can reach the other controllers
        self.host_net = None

    def _detect_slices(self) -> np.ndarray:
        import os
        import sys
        W = self.num_workers
        k = os.environ.get("THRILL_TPU_SLICES")
        if k:
            try:
                k = int(k)
            except ValueError:
                print(f"thrill_tpu: THRILL_TPU_SLICES={k!r} is not an "
                      f"integer; ignoring (single-slice topology)",
                      file=sys.stderr)
                k = 0
            if k == 1:                  # explicit single-slice override
                return np.zeros(W, dtype=np.int64)
            if k > 1:
                if W % k == 0:
                    return np.repeat(np.arange(k), W // k)
                print(f"thrill_tpu: THRILL_TPU_SLICES={k} does not "
                      f"divide {W} workers; ignoring (single-slice "
                      f"topology)", file=sys.stderr)
        ids = [getattr(d, "slice_index", None) for d in self.devices]
        if all(i is not None for i in ids) and len(set(ids)) > 1:
            # normalize to dense 0..nS-1 preserving device order
            uniq = {s: n for n, s in enumerate(dict.fromkeys(ids))}
            return np.array([uniq[i] for i in ids], dtype=np.int64)
        return np.zeros(W, dtype=np.int64)

    # -- controller topology -------------------------------------------
    @property
    def multiprocess(self) -> bool:
        return self.num_processes > 1

    @property
    def local_workers(self):
        """Worker ids whose device this process owns (all of them in a
        single-controller run)."""
        return [w for w in range(self.num_workers)
                if self.worker_process[w] == self.process_index]

    # -- shardings ------------------------------------------------------
    @property
    def sharded(self) -> NamedSharding:
        """Sharding that splits axis 0 across workers."""
        return NamedSharding(self.mesh, P(AXIS))

    @property
    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def put(self, arr) -> jax.Array:
        """Place a host array (leading dim == num_workers) sharded.

        Multi-controller: assembled from per-device addressable shards
        (jax.device_put with a sharded sharding ASSERTS value equality
        across processes — but builds like ReadWordsPacked/ReadBinary
        legitimately hold real data only for their own workers' rows,
        with agreed shapes/counts and zero padding elsewhere)."""
        return self._upload("put", arr, self._place_sharded)

    def _place_sharded(self, arr) -> jax.Array:
        if self.num_processes > 1:
            arr = np.asarray(arr)
            assert arr.shape[0] % self.num_workers == 0, arr.shape
            k = arr.shape[0] // self.num_workers   # rows per worker
            local = [jax.device_put(arr[w * k:(w + 1) * k],
                                    self.devices[w])
                     for w in self.local_workers]
            return jax.make_array_from_single_device_arrays(
                arr.shape, self.sharded, local)
        return jax.device_put(arr, self.sharded)

    def _upload(self, name: str, arr, place: Callable) -> jax.Array:
        """One counted host->device upload through ``place(arr)``, as an
        ``upload`` span. It ends when ``jax.device_put`` returns, which
        may be before the bytes are on the device: nothing here waits
        for them; the ``transfer`` record says when they got there."""
        self.stats_uploads += 1
        nbytes = int(getattr(arr, "nbytes", 0) or 0)
        t0 = time.perf_counter()
        with span_of(self.tracer, "upload", name, bytes=nbytes,
                     shape=list(getattr(arr, "shape", ())),
                     dtype=str(getattr(arr, "dtype", ""))) as sp:
            buf = self._bless(place(arr))
        if sp is not None:
            self._watch().transfer(sp, buf)
        self.stats_upload_s += time.perf_counter() - t0
        self.stats_upload_bytes += nbytes
        return buf

    def _watch(self) -> DeviceWatcher:
        """This mesh's completion watcher (``common/trace.py``), made on
        the first record while the tracer is on; never with it off."""
        w = self._watcher
        if w is None or w.tracer is not self.tracer:
            if w is not None:
                w.stop(0)
            w = self._watcher = DeviceWatcher(self.tracer)
            # its threads end with this mesh where nothing closes it
            weakref.finalize(self, w.stop, 0)
        return w

    def add_noted(self, noted: Dict[str, int], runs: int = 1) -> None:
        """Count ``runs`` runs of a program whose trace noted ``noted``
        (:func:`note`)."""
        self.stats_sort_keys_reused += runs * noted.get(
            "sort_keys_reused", 0)
        self.stats_send_hists_by_compare += runs * noted.get(
            "send_hists_by_compare", 0)

    def flush_device_records(self, timeout: float = 60.0) -> None:
        """Block until every upload and program handed to the watcher so
        far has its ``transfer`` / ``device`` record in the ring."""
        if self._watcher is not None:
            self._watcher.flush(timeout)

    def close_watcher(self, timeout: float = 60.0) -> None:
        """Record what is still in flight and end the watcher's threads
        (``Context.close``)."""
        w, self._watcher = self._watcher, None
        if w is not None:
            w.stop(timeout)

    def keeps_host_memory(self, arr: np.ndarray) -> bool:
        """Whether ``put(arr)`` ([W, ...], one shard per worker) would
        leave a device buffer that IS the host memory, for the buffer's
        life: jax's CPU client does not copy a shard that starts at a
        64-byte aligned address. No other platform's device memory is
        the host's."""
        if self.devices[0].platform != "cpu":
            return False
        base, step = arr.ctypes.data, arr.strides[0]
        return any((base + w * step) % 64 == 0 for w in range(arr.shape[0]))

    def wait_uploaded(self, tree) -> None:
        """Block until the uploads behind ``tree`` are on the device,
        as a ``wait`` span: from then on jax no longer reads the host
        arrays they came from. For a caller that handed ``put`` memory
        it does not own (data/shards.py)."""
        t0 = time.perf_counter()
        with span_of(self.tracer, "wait", "upload"):
            jax.block_until_ready(tree)
        self.stats_sync_wait_s += time.perf_counter() - t0

    def _bless(self, buf: jax.Array) -> jax.Array:
        """Mark a host-uploaded buffer as a legitimate tape constant.
        The loop recorder (api/loop.py) rejects device arrays CREATED
        during a capture iteration — they could be eager host math over
        loop data, which a tape would freeze at iteration-1 values.
        put() is the one host->device choke point, and its numpy input
        is already covered by the fetch-taint + numpy-argument guards,
        so its outputs are safe constants."""
        rec = self.loop_recorder
        if rec is not None:
            rec.bless(buf)
        return buf

    def asarray_blessed(self, leaves):
        """``jnp.asarray`` each non-jax leaf of a dispatch's bound
        operands, blessing the conversions as tape constants. Host
        plan leaves (np bounds/sizes, scalars) converted right before
        a dispatch are legitimate constants by the same argument as
        :meth:`put` uploads — fetched loop-variant values are already
        rejected by the recorder's fetch taint and numpy-argument
        guards. Device leaves pass through with identity preserved so
        the recorder can classify them as carry/val."""
        rec = self.loop_recorder
        out = []
        for l in leaves:
            if not isinstance(l, jax.Array):
                l = jnp.asarray(l)
                if rec is not None:
                    rec.bless(l, operand=True)
            out.append(l)
        return out

    def put_tree(self, tree):
        return jax.tree.map(self.put, tree)

    def put_small(self, arr, replicated: bool = False) -> jax.Array:
        """Content-cached ``put`` for small recurring plan arrays
        (shard counts, zip offsets, range bounds). Iterative pipelines
        re-upload identical tiny arrays every iteration, and device
        buffers are immutable, so sharing one upload per distinct value
        is safe. Falls through to plain put() above 4 KiB.

        ``replicated=True`` places the whole array on every worker
        (P() operand — the exchange plans' [W, W] send matrix form)
        instead of splitting axis 0."""
        arr = np.asarray(arr)
        if arr.nbytes > 4096:
            return self._put_replicated(arr) if replicated \
                else self.put(arr)
        key = (arr.shape, arr.dtype.str, arr.tobytes(), replicated)
        buf = self._put_small_cache.get(key)
        if buf is None:
            if len(self._put_small_cache) >= 4096:   # unbounded-growth cap
                self._put_small_cache.clear()
            buf = self._put_replicated(arr) if replicated \
                else self.put(arr)
            self._put_small_cache[key] = buf
        else:
            self.stats_upload_cache_hits += 1
        return buf

    def _put_replicated(self, arr) -> jax.Array:
        """Upload one identical copy per device (values must already
        agree across processes — exchange plan arrays derive from the
        replicated send matrix, so they do)."""
        return self._upload(
            "put_replicated", np.asarray(arr),
            lambda a: jax.device_put(a, self.replicated))

    def fetch(self, arr) -> np.ndarray:
        """Device -> host fetch that is multi-controller safe.

        ``np.asarray`` raises on arrays spanning non-addressable
        devices (other processes' chips); those are gathered across
        processes first. Single-process meshes take the direct path.
        """
        if isinstance(arr, jax.Array):
            self.stats_fetches += 1
        self.drain_checks()
        return self._fetch_raw(arr, "fetch")

    def drain_checks(self) -> None:
        """Run every queued deferred validation (hinted-join overflow
        recovery and the like). Called by fetch() and by every action
        egress — AllGatherArrays, Sum/_device_reduce(keep_device=True),
        Gather — so no pipeline output can be consumed past an unrun
        check, whatever path it leaves the device by."""
        if not self._pending_checks:
            return
        checks, self._pending_checks = self._pending_checks, []
        try:
            while checks:
                checks.pop(0)()
        except BaseException:
            # a raising check must not discard the unrun tail —
            # a second hinted join's overflow still gets detected
            # at the next fetch even if the caller swallows this one
            self._pending_checks.extend(checks)
            raise

    def reset_run_state(self) -> int:
        """Abandon the aborted pipeline's per-run execution state: the
        deferred-check queue (their producer shards are being
        disposed; a surviving older node's shards still re-validate at
        their own pull — the queue is only the backstop) and any live
        loop-capture recorder. Learned, value-independent state —
        compiled programs, sticky exchange capacities, narrow specs,
        plan kinds — survives: the next pipeline reuses it and stays
        bit-identical to a fresh-Context run by construction. Returns
        the number of checks dropped."""
        dropped = len(self._pending_checks)
        self._pending_checks.clear()
        self.loop_recorder = None
        return dropped

    def _fetch_raw(self, arr, name: str = "check") -> np.ndarray:
        """fetch() without the fetch count or check-draining — for the
        deferred checks themselves (their transfers are tiny, ride a
        completed program, and must not read as mid-pipeline syncs in
        the dispatch-budget accounting).

        A device array is brought over in two timed phases: ``wait``
        (``block_until_ready``: the thread blocked on the device, where
        the copy would block anyway) and ``fetch`` (the copy), the
        latter named ``name``."""
        rec = self.loop_recorder
        if rec is not None:
            # a capture is watching: host plan logic reading a value a
            # recorded dispatch produced may bake loop-VARIANT plan
            # data (exchange send matrices) into the tape — the
            # recorder checks the producer's carry-dependence and
            # rejects such captures (api/loop.py)
            rec.on_fetch(arr)
        if not isinstance(arr, jax.Array):
            return self._copy_to_host(arr)
        tr = self.tracer
        nbytes = int(arr.nbytes)
        t0 = time.perf_counter()
        with span_of(tr, "wait", "device"):
            jax.block_until_ready(arr)
        t1 = time.perf_counter()
        with span_of(tr, "fetch", name, bytes=nbytes):
            out = self._copy_to_host(arr)
        self.stats_sync_wait_s += t1 - t0
        self.stats_fetch_s += time.perf_counter() - t1
        self.stats_fetch_bytes += nbytes
        return out

    def _copy_to_host(self, arr) -> np.ndarray:
        if getattr(arr, "is_fully_addressable", True):
            return np.asarray(arr)
        from jax.experimental import multihost_utils
        return np.asarray(multihost_utils.process_allgather(arr,
                                                            tiled=True))

    def fetch_tree(self, tree):
        return jax.tree.map(self.fetch, tree)

    # -- compiled SPMD programs ----------------------------------------
    def _counted(self, fn: Callable,
                 name: Optional[str] = None) -> "_CountedJit":
        """The one place a jit is constructed. The jitted callable
        carries its label — ``name``, else the tag of the cache key it
        is being built under (``"xchg_chunk"``, ``"sort_fused"``...) —
        so the device plane of a profile reads ``jit_<label>``."""
        label = name or getattr(_TL, "build_tag", None)
        fn = _noting(_named(fn, label))
        return _CountedJit(self, jax.jit(fn), raw=fn, label=label)

    def smap(self, fn: Callable, num_args: int, out_specs=P(AXIS),
             in_specs=None, check_vma: bool = False,
             name: Optional[str] = None) -> Callable:
        """jit(shard_map(fn)) with all-sharded inputs by default.

        Inside ``fn`` every array argument has its leading worker axis
        sliced to size 1 (this worker's shard); collectives use AXIS.
        ``name`` labels the program where the cache key's tag says too
        little (a stitched chain names its ops).
        """
        if in_specs is None:
            in_specs = (P(AXIS),) * num_args
        sm = shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=check_vma)
        # full attribute delegation (not a copied .lower): AOT and
        # introspection callers (.trace, .clone, cost analysis) see
        # the real jit object through the counting proxy; the raw
        # shard_map program rides along for loop-replay donation twins
        # and whole-loop fori lowering (api/loop.py)
        return self._counted(sm, name)

    def jit_cached(self, key: Tuple, fn: Callable) -> Callable:
        """A cached plain-``jax.jit`` program behind the counting
        proxy: replicated (non-shard_map) device math — an iterative
        driver's small update step — becomes a RECORDABLE dispatch the
        loop layer (api/loop.py) can tape and replay, instead of eager
        ops the capture must reject."""
        return self.cached(key, lambda: self._counted(fn))

    def counted_jit(self, fn: Callable) -> "_CountedJit":
        """``jax.jit`` behind the counting proxy, uncached — for
        callers managing their own cache entry (the whole-loop
        fori_loop program, api/loop.py). This and the two methods
        above construct every jit of the codebase (through
        ``_counted``): admission control, the OOM ladder and the
        dispatch counters depend on every device entry passing through
        _CountedJit (pinned by tests/common/test_tracing.py's source
        audit)."""
        return self._counted(fn)

    def cached(self, key: Tuple, builder: Callable[[], Callable]) -> Callable:
        """Memoize a compiled program per (mesh, key).

        DOp implementations use module-level builder functions plus a
        static-parameter key, so re-running a pipeline reuses compiled
        XLA executables (first compile 20-40s on TPU, then cached).
        Trace-time environment knobs that change generated code (the
        sort engine selection) are folded into every key so toggling
        them mid-process takes effect instead of hitting stale programs.
        """
        import os
        key = key + (os.environ.get("THRILL_TPU_SORT_IMPL", "auto"),
                     os.environ.get("THRILL_TPU_SORT_U32"),
                     os.environ.get("THRILL_TPU_PACK_MOVE", "auto"))
        fn = self._cache.get(key)
        if fn is None:
            # the key's tag ("fused", "xchg_chunk"...) names every jit
            # the builder constructs (_counted)
            prev_tag = getattr(_TL, "build_tag", None)
            _TL.build_tag = key[0] if isinstance(key[0], str) else None
            try:
                fn = builder()
            finally:
                _TL.build_tag = prev_tag
            target = fn[0] if isinstance(fn, tuple) else fn
            if isinstance(target, _CountedJit):
                target.cache_key = key
                seed = getattr(self, "_out_bytes_seed", None)
                if seed:
                    # warm restart (service/plan_store.py): the
                    # admission cost model's learned output size for
                    # this program survives the restart — first
                    # dispatches admit on measured bytes instead of
                    # the est_factor cold-start guess
                    from ..data.exchange import _ident_digest
                    v = seed.pop(_ident_digest(key), None)
                    if v is not None:
                        # a bad store value may only cost recompiles,
                        # never a dispatch failure
                        try:
                            target._out_bytes = int(v)
                            self.stats_plan_store_hits += 1
                        except (TypeError, ValueError):
                            pass
                        else:
                            led = self.decisions
                            if led is not None and led.enabled:
                                led.record(
                                    "store_seed",
                                    site="jit:" + target._label(),
                                    chosen="out_bytes",
                                    predicted=target._out_bytes,
                                    reason="warm-start learned size")
            self._cache[key] = fn
        return fn

    # -- plan-state persistence (service/plan_store.py) -----------------
    def export_learned_sizes(self) -> dict:
        """Learned per-program output sizes (the admission cost
        model's ``_out_bytes``) keyed by cache-key digest, plus any
        unconsumed imported seeds."""
        from ..data.exchange import _ident_digest
        out = {}
        for key, fn in self._cache.items():
            target = fn[0] if isinstance(fn, tuple) else fn
            ob = getattr(target, "_out_bytes", None)
            if ob:
                out[_ident_digest(key)] = int(ob)
        for dg, v in (getattr(self, "_out_bytes_seed", None)
                      or {}).items():
            out.setdefault(dg, v)
        return out

    def import_learned_sizes(self, m: dict) -> int:
        seed = getattr(self, "_out_bytes_seed", None)
        if seed is None:
            seed = self._out_bytes_seed = {}
        seed.update({str(k): v for k, v in m.items()})
        return len(m)

    # -- elastic resize (api/context.py Context.resize) -----------------
    def _w_state_attrs(self) -> Tuple[str, ...]:
        """Lazily-created attributes whose values are W-shaped and must
        swap with the worker count: exchange plan state (capacity
        vectors, plan kinds, narrow ranges, store seeds), pre-shuffle
        verdicts, loop tapes (their donation twins are compiled against
        W-sharded buffers), learned output sizes, and the compiled
        program cache itself (every program closes over the mesh)."""
        from ..data.exchange import W_STATE_ATTRS
        return W_STATE_ATTRS + ("_prune_decisions", "_prune_history",
                                "_loop_tapes", "_out_bytes_seed",
                                "_cache")

    def resize(self, devices: Sequence[Any]) -> None:
        """Re-point the executor at a new device set (a new W) at a
        generation boundary. The old W's learned and compiled state is
        ARCHIVED, not discarded, and any state learned the last time
        the new W was active is restored — a W=2→3→2 cycle returns to
        warm plans instead of cold ones. Per-run content caches
        (replicated small uploads, deferred checks, an in-flight loop
        recorder) are device-addressed and simply dropped.

        The caller owns everything above the executor: live shards
        must already be extracted for re-partitioning (the old mesh's
        arrays stay readable — jax arrays carry their sharding — but
        nothing new may be laid out against it), and the host group's
        membership changes through ``net.Group.resize``."""
        devices = list(devices)
        new_w = len(devices)
        if new_w < 1:
            raise ValueError("cannot resize to an empty device set")
        old_w = self.num_workers
        if new_w == old_w and devices == self.devices:
            return
        arch = getattr(self, "_w_archive", None)
        if arch is None:
            arch = self._w_archive = {}
        saved = {}
        for a in self._w_state_attrs():
            if a in self.__dict__:
                saved[a] = self.__dict__.pop(a)
        arch[old_w] = saved
        for a, v in arch.pop(new_w, {}).items():
            setattr(self, a, v)
        if "_cache" not in self.__dict__:
            self._cache = {}
        if "_xchg_plan" not in self.__dict__:
            self._xchg_plan = {}
        self.devices = devices
        self.num_workers = new_w
        self.mesh = Mesh(np.asarray(self.devices), (AXIS,))
        self.slice_id = self._detect_slices()
        self.num_slices = int(self.slice_id.max()) + 1 \
            if len(self.slice_id) else 1
        self.worker_process = np.array(
            [getattr(d, "process_index", 0) for d in self.devices],
            dtype=np.int64)
        self.num_processes = len(set(self.worker_process.tolist())) or 1
        self._put_small_cache.clear()
        self._pending_checks.clear()
        self.loop_recorder = None
