"""Tracing spine: correlated spans across every layer.

The JsonLogger (common/logger.py) records flat event lines; this module
adds the CORRELATION the grown system needs: lightweight spans
(``trace_id``/``span_id``/``parent``) with a category lane per
subsystem, tagged with rank, generation (PR-8 failure domains), tenant
and job name (PR-9 service plane) — so a Perfetto timeline can show
*which dispatch, in which exchange, of which job, on which rank* was on
the critical path.

**The inventory**: every site that records on this spine, and who reads
it. Every record also reaches three readers that take all of them: the
flight recorder's dump (below), ``tools/trace2perfetto.py`` (one lane per
category) and ``Tracer.lane_counts`` (``trace_spans{lane=...}`` on the
metrics endpoint); every span (not instant) reaches
``common/doctor.py critical_path`` (``ctx.doctor_report()``,
``tools/doctor_report.py``). The last column names who reads THAT site
besides: a chip-benchmark metric (``chipbench/layer_metrics/``, through
``chipbench/span_window.py``) or a test that pins it.

Category / name; site; read by:

* ``stage`` / node or action label; ``api/dia_base.py
  DIABase.stage_span`` (``materialize`` around restore-or-compute,
  ``materialize_plan`` around a deferred ``compute_plan``,
  ``staged_action`` around an action), with ``dia_id`` and ``pipe``,
  and ``copied_bytes`` where host arrays were staged under it
  (``data/shards.py _put_staged`` by ``add_to_open``: 0 where they went
  up as views); ``host_plan_s_per_job`` (self time) and the window rule
  (``pipe``), tests/common/test_phase_spans.py,
  tests/data/test_shards_staging.py. One with no span open above it is
  the root of a PULL, counted there as ``overall_stats()["pulls"]``:
  ``pulls_per_job`` (that counter over the jobs) and
  ``replan_gap_s_per_job`` (from one root's last ``fetch`` / ``wait`` to
  the next root's first ``dispatch``), tests/api/test_suffix_rounds.py.
* ``upload`` / ``put``, ``put_replicated``; ``parallel/mesh.py
  MeshExec._upload`` (not the ``put_small`` hit), with ``bytes``,
  ``shape``, ``dtype``; ``upload_s_per_job``, ``upload_bytes_per_job``.
* ``transfer`` / ``put``, ``put_replicated``; :class:`DeviceWatcher`,
  handed the buffer by ``MeshExec._upload``: a child of the ``upload``
  span, from the put's start to the bytes being on the device, with its
  ``bytes``, ``shape``, ``dtype``; ``transfer_s_per_job`` (the union per
  job, GB/s by leaf), ``device_idle_s_per_job`` (the cause
  ``transfer``), tests/common/test_device_records.py.
* ``device`` / program label; :class:`DeviceWatcher`, handed the final
  outputs by ``_CountedJit.__call__``: a child of the ``dispatch`` span,
  from the program's effective start to its outputs being ready
  (``donated`` where they were all donated first);
  ``device_idle_s_per_job`` (what they leave uncovered, and device
  seconds by program), tests/common/test_device_records.py. Neither
  category is a leaf of ``span_window``'s six phases, nor mirrored.
* ``dispatch`` / program label; ``parallel/mesh.py
  _CountedJit.__call__``, every device dispatch, the whole-loop fori
  program included, with ``index_plans``: the ``ReduceToIndex`` index
  plans the program computes in place (``api/fusion.py
  note_index_plans``; 0 on most, and 0 on the whole-loop program, whose
  plans its ``loop`` / ``replay`` span counts): a sorted fold's plan or
  a dense fold's (first arrivals by masked min), the dense ones also
  in ``overall_stats()["r2i_dense_plans"]``, on no span, counted here
  and by ``api/loop.py run_fori`` alike; ``dispatch_call_s_per_job``,
  ``index_plans_per_job``, tests/common/test_trace.py,
  tests/api/test_loop_tree_carry.py,
  tests/api/test_reduce_to_index_dense.py. The
  same choke point counts ``overall_stats()["sort_keys_reused"]``, on
  no span: the sorted key words the program takes from its sort
  (``core/device_sort.py sort_words``, noted at trace time by
  ``parallel/mesh.py note``), and ``api/loop.py run_fori`` the calls'
  in every iteration of a whole-loop dispatch; no metric reads it,
  tests/core/test_sort_words.py does. ``send_hists_by_compare`` alike:
  the send histograms counted by comparison (``data/exchange.py
  send_counts``), tests/data/test_send_hist_compare.py.
* ``compile`` / program label; ``parallel/mesh.py _on_jax_duration``
  (``jax.monitoring``), a backend compile or cache load under a
  dispatch, by ``emit_span``; ``compile_s_in_window``, and taken out of
  ``dispatch_call_s_per_job``.
* ``wait`` / ``device``; ``parallel/mesh.py MeshExec._fetch_raw``,
  blocked on the device before the copy; ``upload``;
  ``MeshExec.wait_uploaded``, blocked until uploads of lent host memory
  are on the device; ``sync_wait_s_per_job``.
* ``fetch`` / ``fetch``, or ``check`` where a deferred check fetches
  uncounted; ``parallel/mesh.py MeshExec._fetch_raw``, the copy, with
  ``bytes``; ``fetch_s_per_job``.
* ``fusion`` / op chain; ``api/fusion.py FusionPlan.execute``, one per
  stitched launch; ``host_plan_s_per_job`` (self time).
* ``exchange`` / ``phase_a``, ``phase_b``, ``optimistic`` (with a verdict
  instant), ``synced``, ``sort_fused``; ``data/exchange.py``,
  ``api/ops/sort.py``; ``phase_b`` and ``sort_fused`` carry
  ``send_slices``, the send blocks their programs cut as slices (what
  ``xchg_send_slices`` counts); ``phase_a`` carries ``rows`` and, from
  ``ReduceByKey`` (``api/ops/reduce.py ReduceNode._post_exchange``),
  ``dup`` (duplicate detection's verdict) and ``regs`` (the presence
  registers' width, 0 without them); ``host_plan_s_per_job`` (self
  time), tests/common/test_doctor.py, test_trace.py (the flight dump
  names it), tests/data/test_exchange_send_slice.py (the field),
  tests/data/test_exchange_row_counters.py (``dup``, ``regs``). On no
  span, counted where an exchange's traffic is accounted
  (``data/exchange.py account_traffic``, once per exchange, a healed
  miss once): ``overall_stats()["xchg_rows_in"]`` (the send matrix's
  total: ``exchange_rows_per_job``) and ``["xchg_rows_local"]`` (its
  trace: ``exchange_local_share``); and where ``ReduceNode`` dispatches
  an exchange whose destination program fills the presence registers,
  ``["dup_detect_exchanges"]``; tests/data/test_exchange_row_counters.py.
* ``plan`` / decision kind (instants); ``common/decisions.py`` (each
  ledger record and audit), ``common/doctor.py`` (skew verdict);
  tests/common/test_doctor.py.
* ``mem`` / ladder rung (instants); ``mem/pressure.py``,
  ``api/fusion.py`` degradations; tests/common/test_trace.py (lane).
* ``stage`` / ``Iterate``; ``api/loop.py Iterate``, the root of a
  loop: of the carry DIA's pipeline (``pipe``, ``dia_id``), or, the
  carry being a pytree of arrays, of its first invariant DIA's (a
  pytree carry with no DIA among the invariants has neither), around
  the carry's first pull and every iteration; ``_LoopCarryNode`` and
  the carry rebuilt after a replay join that pipeline instead of
  starting one, so a job that loops is ONE pipeline;
  ``host_plan_s_per_job`` (self time) and the window rule,
  tests/api/test_loop_spans.py, tests/api/test_loop_tree_carry.py.
* ``loop`` / ``capture`` (one iteration through the pull recursion,
  captured or plain, ``mode``), ``replay`` (one iteration off the
  tape, or ``fori_iters`` of them in one whole-loop dispatch, then
  with ``index_plans``: the plans that dispatch computed, once where
  they are hoisted ahead of the iterations, else in each; ``error``
  where it fell back), ``rebind`` (a call that took over a kept tape:
  its prologue, ``calls``); ``api/loop.py``, children of ``stage`` /
  ``Iterate``, parents of an iteration's dispatches, waits, fetches
  and stages; ``loop_host_s_per_job`` (self time),
  ``loop_captures_in_window``, ``iterations_replayed_share``
  (``chipbench/loop_window.py``), ``index_plans_per_job``,
  tests/api/test_loop_spans.py, tests/api/test_loop_tree_carry.py,
  tests/common/test_trace.py (lane).
* ``service`` / ``queue_wait`` (``emit_span``), ``job:<name>``;
  ``service/scheduler.py``; tests/common/test_trace.py.
* ``net`` / collective site, ``heal``, ``reconnect`` (instant);
  ``net/group.py``, ``net/tcp.py``; tests/common/test_trace.py (lane).
* ``host`` / host frames, ``async_send``; ``data/multiplexer.py``;
  tests/common/test_trace.py (lane).
* ``io`` / ``hbm_restore``; ``mem/hbm.py``; tests/mem/test_hbm_spill.py.

Named scopes (``jax.named_scope``: metadata in the compiled HLO's
``op_name``, no record on this spine, no operation) mark device
operations for a device profile: ``sort_engine``, ``row_move``,
``exchange`` / ``send_slice``, ``exchange`` / ``dest_sort`` (phase A's sort
of the destinations and its gather of every leaf), ``reduce_by_key`` /
``dup_detect`` (the presence registers' fill, psum and takes),
``join_gather``, ``reduce_to_index`` /
``index_plan`` / ``sorted_fold`` / ``dense_fold`` (the dense fold and its
plan), ``segmented_reduce`` / ``run_bounds`` /
``run_fold``, and since PR 36 ``window`` (``api/ops/window.py``: the
slices, the halo and the window function) and ``prefix_sum``
(``api/ops/prefix_sum.py``); read by a throw-away script that joins the
trace to the HLO (PERF.md section 7 item 9b),
tests/api/test_suffix_rounds.py (``window``, ``prefix_sum``, in the
lowered text), tests/data/test_exchange_row_counters.py (``dest_sort``,
``dup_detect``, likewise).

No cell of the benchmark runs the last four entries' planes; what nothing
reads by name is listed under ROADMAP D7 for the PR that folds the
observability mechanisms (``front_door`` / ``admit`` and
``stream:<name>``, ``io`` / ``writeback`` and ``prefetch_reader`` went in
PR 38: nothing read them).

A span of the categories in :data:`MIRRORED` is also a
``jax.profiler.TraceAnnotation``; a record carries ``ts`` (wall-anchored
microseconds, for the log files and the device trace) and ``t0_s`` (its
start on ``time.perf_counter()``, the clock of a reader in the process).
``ts`` is the profiler's clock (a device plane's event times are
relative to its ``profile_start_time``, wall-clock nanoseconds): a
``device`` record's end by ``ts`` (``ts + dur_us``) against the end of
its ``jit_<label>`` module on the device plane of a trace taken beside
it is the rule for laying the program's spans on the trace; its offset
is NOT yet measured (PERF.md section 7 item 35).

Spans emit through the existing JsonLogger as ``event=span`` lines
(json2profile ignores unknown events, so the HTML report keeps
working) and ``tools/trace2perfetto.py`` exports Chrome-trace-event
JSON — one pid lane per rank, one tid lane per subsystem — that loads
directly in Perfetto / chrome://tracing.

Two always-on companions make this production-shaped:

* **Flight recorder**: every finished span/instant also lands in a
  bounded in-memory ring (``THRILL_TPU_TRACE_RING`` records, default
  512 — a deque append, near-zero cost when file logging is off). The
  moment a pipeline aborts (PipelineError/ClusterAbort/unrecoverable
  verdict, api/context.py hooks) the ring dumps to a timestamped file
  under ``THRILL_TPU_FLIGHT_DIR`` — a self-contained post-mortem whose
  final spans name the failing site and generation. The dump header
  records the THRILL_TPU_FAULTS arming, so chaos-sweep archives carry
  the seed that produced each failure.
* **Live metrics**: common/metrics.py serves ``overall_stats`` +
  service gauges in Prometheus text format from a daemon thread
  (``THRILL_TPU_METRICS_PORT``).

Overhead contract: ``THRILL_TPU_TRACE=0`` is a pinned no-op fast path
— the dispatch choke point pays ONE attribute read plus one predicate
check and allocates no span objects (tests/common/test_trace.py pins
this via the module's ``SPANS_CREATED`` counter) and no
:class:`DeviceWatcher`: no thread, no queue (test_device_records.py).
With it on, the watcher costs each upload and dispatch one queue append
and each completion a wake-up of a lane's thread: PERF.md section 5 has
``records_per_s`` with the Tracer off and on.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import json
import os
import threading
import time
import weakref
from typing import Any, Dict, Optional

from jax.profiler import TraceAnnotation

#: total Span objects ever allocated in this process — the pin the
#: THRILL_TPU_TRACE=0 no-op test asserts stays flat across dispatches
SPANS_CREATED = 0

#: shared do-nothing context manager for the disabled path (stateless,
#: so one instance serves every call site)
_NULL = contextlib.nullcontext()

_FLIGHT_SEQ = itertools.count()

#: categories whose spans are also ``jax.profiler.TraceAnnotation``s:
#: the program's host phases, a few per job, never per row / chunk /
#: tile. In a profile taken with the host's tracer on they lie on the
#: profiler's clock beside the device plane; with
#: ``host_tracer_level=0`` (every benchmark run) they vanish by design.
#: ``compile`` spans are known only afterwards (``emit_span``) and have
#: no mirror: the profiler carries JAX's own compile events.
MIRRORED = frozenset(("stage", "upload", "wait", "fetch", "exchange"))

#: the process's most recently constructed Tracer (see :func:`latest`)
_LATEST: Optional["Tracer"] = None


def latest() -> Optional["Tracer"]:
    """The process's most recently constructed :class:`Tracer`, still
    reachable after ``Run()`` has returned and its Context is closed:
    a reader in the process (the chip benchmark's span metrics) takes
    the finished run's records from ``latest().ring`` and asks
    ``latest().wrapped`` whether the ring still holds all of them."""
    return _LATEST


def trace_enabled() -> bool:
    """THRILL_TPU_TRACE=0 disables span creation everywhere (read once
    per Tracer, at Context construction)."""
    from .config import _env_flag
    return _env_flag("THRILL_TPU_TRACE", True)


def _env_int_clamped(name: str, default: int, lo: int) -> int:
    from .config import _env_int
    try:
        return max(_env_int(name, default), lo)
    except ValueError:
        return default


def ring_capacity() -> int:
    """THRILL_TPU_TRACE_RING: flight-recorder ring size in records
    (default 512; 0 disables the ring and with it the flight dumps)."""
    return _env_int_clamped("THRILL_TPU_TRACE_RING", 512, 0)


def flight_dir() -> Optional[str]:
    """Directory flight-recorder dumps land in. Default: a per-USER
    stable path under the system temp dir (the recorder is always on;
    a shared fixed path would be owned by whichever user ran first and
    silently unwritable for everyone else);
    ``THRILL_TPU_FLIGHT_DIR=0|off|none`` disables dumps entirely."""
    v = os.environ.get("THRILL_TPU_FLIGHT_DIR")
    if v in ("0", "off", "none"):
        return None
    if v:
        return v
    import tempfile
    uid = getattr(os, "getuid", lambda: "u")()
    return os.path.join(tempfile.gettempdir(),
                        f"thrill_tpu_flight-{uid}")


def _flight_keep() -> int:
    """Newest-N dump files kept per directory (THRILL_TPU_FLIGHT_KEEP,
    default 40) — an abort-heavy chaos sweep must not fill the disk."""
    return _env_int_clamped("THRILL_TPU_FLIGHT_KEEP", 40, 1)


class Span:
    """One timed region. Context-manager: exceptions escaping the block
    are recorded as an ``error`` attribute before the span finishes —
    the flight recorder's final spans name the failing site this way."""

    __slots__ = ("tracer", "span_id", "parent", "cat", "name", "ts_us",
                 "t0", "t1", "attrs", "generation", "tenant", "job",
                 "ann")

    def __init__(self, tracer: "Tracer", span_id: int,
                 parent: Optional[int], cat: str, name: str,
                 attrs: Dict[str, Any]) -> None:
        self.tracer = tracer
        self.span_id = span_id
        self.parent = parent
        self.cat = cat
        self.name = name
        self.attrs = attrs
        self.ts_us = tracer._now_us()
        self.t0 = time.perf_counter()
        self.t1: Optional[float] = None
        self.generation = tracer.gen_fn() if tracer.gen_fn is not None \
            else None
        self.tenant = tracer.tenant_fn() if tracer.tenant_fn is not None \
            else None
        self.job = tracer.current_job
        self.ann = None
        if cat in MIRRORED:
            self.ann = TraceAnnotation(f"{cat}:{name}")
            self.ann.__enter__()

    def close_mirror(self) -> None:
        ann, self.ann = self.ann, None
        if ann is not None:
            ann.__exit__(None, None, None)

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, et, ev, tb) -> None:
        if ev is not None:
            self.attrs["error"] = repr(ev)[:200]
        self.tracer.end(self)

    def rec(self) -> dict:
        r = {"event": "span", "cat": self.cat, "name": self.name,
             "trace": self.tracer.trace_id, "span": self.span_id,
             "rank": self.tracer.rank, "ts": self.ts_us,
             # the start on time.perf_counter()'s clock, which is the
             # clock of a reader in the process (ts is for the logs)
             "t0_s": self.t0,
             "dur_us": int(((self.t1 if self.t1 is not None
                             else time.perf_counter()) - self.t0) * 1e6)}
        if self.parent is not None:
            r["parent"] = self.parent
        if self.generation is not None:
            r["generation"] = self.generation
        if self.tenant is not None:
            r["tenant"] = self.tenant
        if self.job is not None:
            r["job"] = self.job
        r.update(self.attrs)
        return r


class Tracer:
    """Per-Context span factory + flight-recorder ring.

    Attached as ``mesh_exec.tracer`` / ``net.group.tracer`` /
    ``ctx.tracer`` so every choke point reaches it in one attribute
    read; ``enabled`` False (THRILL_TPU_TRACE=0) makes every guarded
    site skip span allocation entirely. Propagation is EXPLICIT: a
    per-thread span stack supplies the parent id; cross-thread workers
    (the async host sender) pass ``parent=`` captured on the
    submitting thread."""

    def __init__(self, rank: int = 0, logger=None,
                 ring: Optional[int] = None,
                 enabled: Optional[bool] = None) -> None:
        self.enabled = trace_enabled() if enabled is None else enabled
        self.rank = rank
        self.logger = logger
        cap = ring_capacity() if ring is None else ring
        self.ring: Optional[collections.deque] = \
            collections.deque(maxlen=cap) if cap > 0 else None
        self.trace_id = f"{os.getpid():x}.{rank}"
        self._ids = itertools.count(1)
        self._tls = threading.local()
        # context binders (Context sets them): generation / tenant of
        # the moment a span STARTS; the scheduler sets current_job
        # around each served job so nested spans carry the job name
        self.gen_fn = None
        self.tenant_fn = None
        self.current_job: Optional[str] = None
        # finished spans per category lane (the metrics endpoint's
        # trace_spans{lane=...})
        self.lane_counts: Dict[str, int] = {}
        # records ever written to the ring, against its capacity
        self.records_written = 0
        self._record_lock = threading.Lock()
        global _LATEST
        _LATEST = self
        if logger is not None and hasattr(logger, "now_us"):
            self._now_us = logger.now_us
        else:
            wall0, perf0 = time.time(), time.perf_counter()
            self._now_us = lambda: int(
                (wall0 + time.perf_counter() - perf0) * 1e6)

    @property
    def wrapped(self) -> bool:
        """The ring has dropped records: it no longer holds every span
        since this Tracer was constructed (always true without a
        ring)."""
        return self.ring is None \
            or self.records_written > self.ring.maxlen

    # -- span lifecycle -------------------------------------------------
    def _stack(self) -> list:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def current_id(self) -> Optional[int]:
        """The calling thread's innermost open span id (for explicit
        cross-thread parenting)."""
        st = getattr(self._tls, "stack", None)
        return st[-1].span_id if st else None

    def span(self, cat: str, name: str, parent: Optional[int] = None,
             **attrs: Any) -> Span:
        """Open a span (use as a context manager). ``parent`` defaults
        to the calling thread's innermost open span."""
        return self.begin(cat, name, parent=parent, **attrs)

    def begin(self, cat: str, name: str, parent: Optional[int] = None,
              **attrs: Any) -> Span:
        """Open a span without the context-manager protocol (callers
        with early-exit control flow pair it with ``end`` in a
        try/finally)."""
        global SPANS_CREATED
        SPANS_CREATED += 1
        st = self._stack()
        if parent is None and st:
            parent = st[-1].span_id
        sp = Span(self, next(self._ids), parent, cat, name, attrs)
        st.append(sp)
        return sp

    def end(self, sp: Span, **attrs: Any) -> None:
        sp.t1 = time.perf_counter()
        if attrs:
            sp.attrs.update({k: v for k, v in attrs.items()
                             if v is not None})
        st = getattr(self._tls, "stack", None)
        if st:
            # pop the span plus anything leaked above it (an exception
            # that skipped a child's end must not corrupt parenting)
            for i in range(len(st) - 1, -1, -1):
                if st[i] is sp:
                    for leaked in reversed(st[i + 1:]):
                        leaked.close_mirror()
                    del st[i:]
                    break
        sp.close_mirror()
        self._record(sp.rec())

    def add_to_open(self, cat: str, key: str, amount: int) -> None:
        """Add ``amount`` to attribute ``key`` of the calling thread's
        innermost open span of category ``cat`` (so an amount of 0
        still leaves the attribute on the record); nothing where
        tracing is off or no such span is open."""
        if not self.enabled:
            return
        for sp in reversed(self._stack()):
            if sp.cat == cat:
                sp.attrs[key] = sp.attrs.get(key, 0) + amount
                return

    def emit_span(self, cat: str, name: str, start_s: float,
                  end_s: float, parent: Optional[int] = None,
                  **attrs: Any) -> None:
        """Record an already-elapsed region measured with
        ``time.perf_counter()`` (the scheduler's queue-wait bar: the
        wait happened before the span could be opened)."""
        if not self.enabled:
            return
        now_us = self._now_us()
        elapsed_us = int(max(time.perf_counter() - start_s, 0.0) * 1e6)
        rec = {"event": "span", "cat": cat, "name": name,
               "trace": self.trace_id, "span": next(self._ids),
               "rank": self.rank, "ts": now_us - elapsed_us,
               "t0_s": start_s,
               "dur_us": int(max(end_s - start_s, 0.0) * 1e6)}
        if parent is not None:
            rec["parent"] = parent
        if self.gen_fn is not None:
            rec["generation"] = self.gen_fn()
        rec.update({k: v for k, v in attrs.items() if v is not None})
        self._record(rec)

    def instant(self, cat: str, name: str, **attrs: Any) -> None:
        """Zero-duration marker (ladder rungs, exchange verdicts)."""
        if not self.enabled:
            return
        rec = {"event": "span", "kind": "instant", "cat": cat,
               "name": name, "trace": self.trace_id,
               "span": next(self._ids), "rank": self.rank,
               "ts": self._now_us(), "dur_us": 0}
        pid = self.current_id()
        if pid is not None:
            rec["parent"] = pid
        if self.gen_fn is not None:
            rec["generation"] = self.gen_fn()
        if self.tenant_fn is not None:
            t = self.tenant_fn()
            if t is not None:
                rec["tenant"] = t
        if self.current_job is not None:
            rec["job"] = self.current_job
        rec.update({k: v for k, v in attrs.items() if v is not None})
        self._record(rec)

    def _record(self, rec: dict) -> None:
        # instants count toward the lane totals too (the mem lane is
        # emitted EXCLUSIVELY as instants); the lock because a
        # DeviceWatcher records from its own thread
        with self._record_lock:
            cat = rec["cat"]
            self.lane_counts[cat] = self.lane_counts.get(cat, 0) + 1
            if self.ring is not None:
                self.ring.append(rec)
                self.records_written += 1
        log = self.logger
        if log is not None and log.enabled:
            log.line(**rec)

    # -- flight recorder ------------------------------------------------
    def dump_flight(self, reason: Any, generation: Optional[int] = None
                    ) -> Optional[str]:
        """Write the ring's records to a timestamped post-mortem file.
        Best-effort by contract: returns the path, or None when the
        recorder is disabled (tracing off / no ring /
        THRILL_TPU_FLIGHT_DIR=0), the ring is empty (a header-only
        dump would only churn the keep-N rotation — the TRACE=0 abort
        path writes nothing), or the write fails — a failing dump must
        never mask the abort being recorded."""
        if not self.enabled or not self.ring:
            return None
        d = flight_dir()
        if d is None:
            return None
        recs = list(self.ring)
        from . import faults
        header = {"event": "flight_header",
                  "reason": str(reason)[:300],
                  "generation": generation, "rank": self.rank,
                  "trace": self.trace_id, "ts": self._now_us(),
                  "records": len(recs),
                  "faults": os.environ.get(faults.ENV_VAR) or None}
        name = (f"flight-{int(time.time() * 1e3)}-p{os.getpid()}"
                f"-r{self.rank}-{next(_FLIGHT_SEQ)}.json")
        path = os.path.join(d, name)
        try:
            os.makedirs(d, exist_ok=True)
            with open(path, "w") as f:
                f.write(json.dumps(header, default=str) + "\n")
                for r in recs:
                    f.write(json.dumps(r, default=str) + "\n")
        except OSError:
            return None
        try:
            _prune(d, _flight_keep())
        except OSError:
            pass
        return path


class DeviceWatcher:
    """When each upload and each program is done on the device.

    One per ``MeshExec`` (``parallel/mesh.py MeshExec._watch``), made on
    the first hand-off while ``tracer.enabled`` is true: with
    ``THRILL_TPU_TRACE=0`` nothing makes one, so there is no thread, no
    queue and no allocation. ``MeshExec._upload`` hands it the placed
    buffer with its ``upload`` span, ``_CountedJit.__call__`` a
    program's final outputs and arguments with its ``dispatch`` span;
    each hand-off is one queue append. The watcher waits until each is
    ready and records, through :meth:`Tracer.emit_span`, parented to
    that span:

    * ``transfer`` / ``put`` or ``put_replicated``: from the put's start
      to the bytes being on the device, with ``bytes``, ``shape``,
      ``dtype``;
    * ``device`` / program label: from the program's effective start to
      its outputs being ready. The effective start is the latest of its
      dispatch (taken when the call has handed the program to the
      runtime: the call's own Python before that, 0.4 ms a dispatch in
      ``suffix.w1``, is no device time), the previous program's ready
      on this mesh, and the ready of every transfer whose buffer is
      among its arguments, told by identity. Where an argument is a
      device array that came from neither a transfer nor a program the
      watcher saw (an eager ``jnp`` result, a buffer from before the
      watcher started), what it depends on cannot be told, and the
      ready of every transfer issued before the program counts instead.

    Two lanes, each one daemon thread, started with the watcher, taking
    its entries in order until :meth:`stop` (``Context.close``, or the
    ``MeshExec`` going away): transfers and programs
    run on separate streams of the device, and one thread in order
    would close a transfer issued behind a running program at that
    program's end (a 1 MB put 249 ms late on the CPU). The program lane
    waits for a program's outputs first and only then for the transfer
    lane to have recorded every transfer issued before it.

    Donation: a loop's replay hands outputs to donating twins, so the
    watcher waits on any output that is still alive (a program's leaves
    are ready together) and never holds a donation up (it keeps a
    reference, no hold). Where none is alive, a program's record is
    closed at the next ready the program lane sees, which is no later
    than its consumer's, and carries ``donated`` (a transfer's at the
    moment that is seen).

    The GIL: the end is taken when the wait returns, which is late by up
    to the interpreter's switch interval (5 ms) while the dispatching
    thread runs Python, and exact while it is blocked (a ``wait``, a
    caller's ``block_until_ready``). Against the device trace: NOT yet
    measured (PERF.md section 7 item 35); device seconds over the
    trace's busy time read +0.08 to +0.88 % a job on the chip."""

    LANES = ("transfer", "device")

    def __init__(self, tracer: "Tracer") -> None:
        import queue
        self.tracer = tracer
        self._lock = threading.Lock()       # hand-offs in order
        self._queues = {lane: queue.SimpleQueue() for lane in self.LANES}
        self._handed = 0            # transfers handed over
        # the transfer lane's state, read by the program lane under it
        self._settled = threading.Condition()
        self._recorded = 0          # transfers recorded, in order
        self._last_transfer = 0.0   # the latest transfer's ready
        # transfers whose ready may lie after a later dispatch's start:
        # id -> (weak reference, ready)
        self._transfers: Dict[int, tuple] = {}
        # live buffers from a transfer or a program: id -> weak reference
        self._known: Dict[int, Any] = {}
        self._last_ready = 0.0      # the previous program's ready
        self._pending: list = []    # programs whose outputs were donated
        self._threads = [threading.Thread(
            target=self._run, args=(lane,), daemon=True,
            name=f"thrill-tpu-watch-{lane}") for lane in self.LANES]
        for t in self._threads:
            t.start()

    # -- the dispatching threads ----------------------------------------
    def transfer(self, span: Span, buf) -> None:
        self._put("transfer", span, buf, None)

    def device(self, span: Span, out, args) -> None:
        # the dispatch call has handed the program to the runtime: the
        # call's own Python before that is no device time
        self._put("device", span, out, (args, time.perf_counter()))

    def flush(self, timeout: Optional[float] = None) -> bool:
        """Block until every entry handed over so far is recorded (a
        program whose outputs were all donated is closed now)."""
        return self._signal("flush", timeout)

    def stop(self, timeout: Optional[float] = None) -> bool:
        """:meth:`flush`, then end the threads (``Context.close``)."""
        return self._signal("stop", timeout)

    def _signal(self, kind: str, timeout: Optional[float]) -> bool:
        events = [threading.Event() for _ in self.LANES]
        for lane, done in zip(self.LANES, events):
            self._put(lane, None, done, kind)
        return all(done.wait(timeout) for done in events)

    def _put(self, lane: str, span, payload, args) -> None:
        with self._lock:
            if span is not None and lane == "transfer":
                self._handed += 1
            self._queues[lane].put((span, payload, args, self._handed))

    # -- the lanes' threads ---------------------------------------------
    def _run(self, lane: str) -> None:
        q = self._queues[lane]
        settle = self._settle_transfer if lane == "transfer" \
            else self._settle_device
        while True:
            span, payload, args, n = q.get()
            if span is None:                # flush or stop
                if lane == "device":
                    self._close_pending(time.perf_counter())
                payload.set()
                if args == "stop":
                    return
                continue
            try:
                settle(span, payload, args, n)
            except Exception as e:          # never let a lane die
                self.tracer.emit_span(lane, span.name, span.t0,
                                      time.perf_counter(),
                                      parent=span.span_id,
                                      error=repr(e)[:200])
            # let go of the buffers now, not when the next entry comes:
            # held over an idle lane they raised the peak of HBM by a
            # job's input and output (terasort.w1: 3.86 -> 5.74 GB)
            span = payload = args = None

    def _settle_transfer(self, span: Span, buf, _args, n: int) -> None:
        ready = None
        try:
            ready, error = self._wait([buf])
            self.tracer.emit_span(
                "transfer", span.name, span.t0,
                time.perf_counter() if ready is None else ready,
                parent=span.span_id, error=error,
                donated=True if ready is None else None,
                **{k: span.attrs.get(k) for k in ("bytes", "shape", "dtype")})
            if ready is not None:
                self._remember([buf])
        finally:
            with self._settled:
                if ready is not None:
                    self._transfers[id(buf)] = (weakref.ref(buf), ready)
                    self._last_transfer = max(self._last_transfer, ready)
                self._recorded = n
                self._settled.notify_all()

    def _settle_device(self, span: Span, out, args, n: int) -> None:
        import jax
        leaves = [l for l in jax.tree.leaves(out) if isinstance(l, jax.Array)]
        ready, error = self._wait(leaves)
        if ready is None:
            self._pending.append((span, self._start(span, args, n)))
            return
        self._close_pending(ready)
        start = min(self._start(span, args, n), ready)
        self.tracer.emit_span("device", span.name, start, ready,
                              parent=span.span_id, error=error)
        self._last_ready = max(self._last_ready, ready)
        self._remember(leaves)

    def _start(self, span: Span, args, n: int) -> float:
        """The program's effective start (the class docstring), once the
        transfer lane has recorded the ``n`` transfers issued before."""
        import jax
        args, returned = args
        start = max(returned, self._last_ready)
        told = True
        with self._settled:
            self._settled.wait_for(lambda: self._recorded >= n, 60.0)
            for leaf in jax.tree.leaves(args):
                if not isinstance(leaf, jax.Array):
                    continue
                hit = self._transfers.get(id(leaf))
                if hit is not None and hit[0]() is leaf:
                    start = max(start, hit[1])
                elif id(leaf) not in self._known:
                    told = False
            if not told:
                start = max(start, self._last_transfer)
            # a transfer on the device before this dispatch began cannot
            # move a later one
            self._transfers = {k: v for k, v in self._transfers.items()
                               if v[1] > returned}
        return start

    def _remember(self, leaves) -> None:
        known = self._known
        for leaf in leaves:
            known[id(leaf)] = weakref.ref(
                leaf, lambda _, i=id(leaf): known.pop(i, None))

    @staticmethod
    def _wait(leaves):
        """(ready, error) of the first output still alive; (None, None)
        where every one was donated."""
        for leaf in leaves:
            try:
                if leaf.is_deleted():
                    continue
                leaf.block_until_ready()
            except Exception as e:
                if leaf.is_deleted():
                    continue
                return time.perf_counter(), repr(e)[:200]
            return time.perf_counter(), None
        return None, None

    def _close_pending(self, ready: float) -> None:
        pending, self._pending = self._pending, []
        for span, start in pending:
            self.tracer.emit_span("device", span.name, min(start, ready),
                                  ready, parent=span.span_id, donated=True)
            self._last_ready = max(self._last_ready, ready)


def _prune(d: str, keep: int) -> None:
    """Drop all but the newest ``keep`` flight dumps in ``d`` — along
    with each pruned dump's decision-ledger sibling
    (``decisions-*.json``, common/decisions.py), which would otherwise
    accumulate unboundedly under an abort-heavy chaos sweep."""
    files = [os.path.join(d, f) for f in os.listdir(d)
             if f.startswith("flight-") and f.endswith(".json")]
    if len(files) <= keep:
        return
    files.sort(key=lambda p: os.path.getmtime(p), reverse=True)
    for p in files[keep:]:
        for victim in (p, os.path.join(
                os.path.dirname(p), "decisions-"
                + os.path.basename(p)[len("flight-"):])):
            try:
                os.unlink(victim)
            except OSError:
                pass


def span_of(tracer: Optional[Tracer], cat: str, name: str,
            **attrs: Any):
    """``tracer.span(...)`` when tracing is live, the shared null
    context otherwise — the one-liner guard for call sites where a
    with-block reads best."""
    if tracer is not None and tracer.enabled:
        return tracer.span(cat, name, **attrs)
    return _NULL


def instant_of(tracer: Optional[Tracer], cat: str, name: str,
               **attrs: Any) -> None:
    """Guarded instant: the one-liner the marker sites (ladder rungs,
    reconnects, fusion degradations) share instead of each carrying
    the None/enabled check."""
    if tracer is not None and tracer.enabled:
        tracer.instant(cat, name, **attrs)
