"""Plan observatory: the framework-wide decision ledger.

The port now makes ~ten data-driven plan choices deep inside the stack
— fusion split points and barrier reasons (api/fusion.py,
api/dia_base.py), bulk/chunked/1-factor exchange strategy, chunk count
K, narrow specs and the optimistic-vs-synced verdict
(data/exchange.py), pre-shuffle prune verdicts (core/preshuffle.py),
HBM admission estimates (mem/pressure.py + parallel/mesh.py), plan-
store seed consumption and skips (service/plan_store.py,
api/context.py). Each used to decide silently, auditable only by
reading code. This module makes every one of them a first-class
record:

* :class:`DecisionRecord` — site key, kind, inputs, predicted value,
  chosen alternative, rejected alternatives with their estimated
  costs, and (once truth arrives) the joined actual with a
  ``log2(predicted/actual)`` error.
* :class:`DecisionLedger` — one per Context, attached as
  ``mesh_exec.decisions`` so every choke point reaches it in one
  attribute read. Records land in a bounded ring
  (``THRILL_TPU_DECISIONS_RING``, default 4096), as ``event=decision``
  JSON log lines, and as instants on the tracing spine's ``plan`` lane
  (common/trace.py) — Perfetto shows *why* alongside *when*.
* Joins happen at the points where truth arrives: the optimistic
  exchange's deferred capacity check, the dispatch choke point's
  measured output bytes, observed prune fractions (record_prune).
  Per-kind ``|log2(pred/actual)|`` aggregates feed the accuracy
  ledger in ``ctx.overall_stats()`` (``decision_accuracy``) and
  ``PlanStore.save_ledger`` — the on-disk audit trail next to
  plans.json.
* :func:`render_plan` — the shared explain() renderer: an annotated
  physical-plan tree (ops, fused segments, exchange strategy per
  edge, every decision with its reason and audit verdict). Consumed
  live by ``ctx.explain()`` / ``DIA.explain()`` and offline by
  ``tools/plan_report.py`` over JSON logs.

Overhead contract: ``THRILL_TPU_DECISIONS=0`` is a pinned no-op — the
dispatch choke point pays one attribute read plus one predicate and
allocates no record objects (tests/common/test_decisions.py pins this
via :data:`RECORDS_CREATED`, the SPANS_CREATED pattern). Decisions are
observability, never load-bearing: a dropped or ring-evicted record
changes no plan.

This ledger is the direct prerequisite for the ROADMAP's cost-based
adaptive planner: a cost model you can audit is one you can let
choose.
"""

from __future__ import annotations

import collections
import itertools
import json
import math
import os
import threading
from typing import Any, Dict, List, Optional, Tuple

from .stats import Aggregate

#: total DecisionRecord objects ever allocated in this process — the
#: THRILL_TPU_DECISIONS=0 no-op test asserts this stays flat across
#: dispatches (the SPANS_CREATED pattern, common/trace.py)
RECORDS_CREATED = 0

#: audit-verdict error threshold: |log2(pred/actual)| <= 1 (within 2x)
#: reads "ok", anything past it "off" — coarse by design; the MAE
#: aggregates carry the real number
_OK_LOG2 = 1.0


def decisions_enabled() -> bool:
    """THRILL_TPU_DECISIONS=0 disables the whole ledger (read once per
    ledger, at Context construction)."""
    from .config import _env_flag
    return _env_flag("THRILL_TPU_DECISIONS", True)


def ring_capacity() -> int:
    """THRILL_TPU_DECISIONS_RING: in-memory record ring size (default
    4096; explain() sees at most this many recent records — the
    per-kind counters and accuracy aggregates never drop)."""
    from .config import _env_int
    try:
        return max(_env_int("THRILL_TPU_DECISIONS_RING", 4096), 0)
    except ValueError:
        return 4096


class DecisionRecord:
    """One plan choice: what was decided, from which inputs, what the
    model predicted, what else was on the table — and, once truth
    arrives, how wrong the prediction was."""

    __slots__ = ("seq", "kind", "site", "chosen", "predicted",
                 "rejected", "reason", "inputs", "dia", "node",
                 "actual", "err_log2", "verdict")

    def __init__(self, seq: int, kind: str, site: str, chosen: str,
                 predicted: Optional[float], rejected, reason,
                 inputs: Dict[str, Any], dia: Optional[int],
                 node: Optional[str]) -> None:
        self.seq = seq
        self.kind = kind
        self.site = site
        self.chosen = chosen
        self.predicted = predicted
        self.rejected = rejected     # [(alternative, est_cost), ...]
        self.reason = reason
        self.inputs = inputs
        self.dia = dia
        self.node = node
        self.actual: Optional[float] = None
        self.err_log2: Optional[float] = None
        self.verdict: Optional[str] = None

    def rec(self) -> dict:
        """JSON-log form (the ``event=decision`` line; also what
        tools/plan_report.py reconstructs records from)."""
        r: Dict[str, Any] = {"event": "decision", "seq": self.seq,
                             "kind": self.kind, "site": self.site,
                             "chosen": self.chosen}
        if self.predicted is not None:
            r["predicted"] = self.predicted
        if self.rejected:
            r["rejected"] = [[a, c] for a, c in self.rejected]
        if self.reason:
            r["reason"] = self.reason
        if self.inputs:
            r["inputs"] = self.inputs
        if self.dia is not None:
            r["dia_id"] = self.dia
        if self.node is not None:
            r["node"] = self.node
        return r

    def audit_rec(self) -> dict:
        r: Dict[str, Any] = {"event": "decision_audit", "seq": self.seq,
                             "kind": self.kind, "site": self.site,
                             "verdict": self.verdict}
        if self.actual is not None:
            r["actual"] = self.actual
        if self.err_log2 is not None:
            r["err_log2"] = round(self.err_log2, 4)
        return r


class DecisionLedger:
    """Per-Context decision store + predicted-vs-actual accuracy
    aggregates. Attached as ``mesh_exec.decisions`` (one attribute
    read per choke point); ``enabled`` False makes every guarded site
    allocate nothing."""

    def __init__(self, logger=None, tracer=None,
                 ring: Optional[int] = None,
                 enabled: Optional[bool] = None) -> None:
        self.enabled = decisions_enabled() if enabled is None \
            else enabled
        self.logger = logger
        self.tracer = tracer
        # audit subscriber (api/planner.py Planner.on_audit): called
        # with every record whose actual just joined, so the adaptive
        # planner can act on predictions that turned out to be lies.
        # None (no planner / THRILL_TPU_PLANNER=0) = pure observatory.
        self.audit_hook = None
        cap = ring_capacity() if ring is None else ring
        self.records: collections.deque = collections.deque(
            maxlen=cap if cap > 0 else 1)
        self._ids = itertools.count(1)
        self._lock = threading.Lock()
        # never-evicted aggregates: per-kind record counts, per-kind
        # joined counts + |log2 err| stats, per-(kind, site) audit
        # means (the worst-sites table)
        self.kind_counts: Dict[str, int] = {}
        self.joined_counts: Dict[str, int] = {}
        self._acc: Dict[str, Aggregate] = {}
        self._site_err: Dict[Tuple[str, str], List[float]] = {}
        # open records awaiting a resolve_site() join from a different
        # scope (prune verdicts: recorded at plan time, audited when
        # record_prune observes the fraction)
        self._open: Dict[Tuple[str, str], DecisionRecord] = {}
        # current DIA node (thread-local stack; dia_base.materialize
        # binds it around compute so decisions recorded inside land on
        # the right node in explain())
        self._tls = threading.local()

    # -- node binding ---------------------------------------------------
    def push_node(self, dia_id: int, label: str) -> None:
        st = getattr(self._tls, "nodes", None)
        if st is None:
            st = self._tls.nodes = []
        st.append((dia_id, label))

    def pop_node(self) -> None:
        st = getattr(self._tls, "nodes", None)
        if st:
            st.pop()

    def _current_node(self) -> Tuple[Optional[int], Optional[str]]:
        st = getattr(self._tls, "nodes", None)
        return st[-1] if st else (None, None)

    # -- recording ------------------------------------------------------
    def record(self, kind: str, site: str, chosen: str,
               predicted: Optional[float] = None,
               rejected=None, reason: Optional[str] = None,
               join: bool = False, dia: Optional[int] = None,
               node: Optional[str] = None,
               **inputs: Any) -> DecisionRecord:
        """Record one plan choice. ``join=True`` keeps the record open
        under (kind, site) for a later :meth:`resolve_site`; callers
        holding the record in scope pass it to :meth:`resolve`
        directly. ``dia``/``node`` override the thread-local current
        node (fusion-barrier records are ABOUT a node, not recorded
        inside its compute)."""
        global RECORDS_CREATED
        RECORDS_CREATED += 1
        if dia is None:
            dia, node = self._current_node()
        rec = DecisionRecord(next(self._ids), kind, site, chosen,
                             _num(predicted), rejected, reason,
                             inputs, dia, node)
        with self._lock:
            self.records.append(rec)
            self.kind_counts[kind] = self.kind_counts.get(kind, 0) + 1
            if join:
                self._open[(kind, site)] = rec
        log = self.logger
        if log is not None and log.enabled:
            log.line(**rec.rec())
        tr = self.tracer
        if tr is not None and tr.enabled:
            tr.instant("plan", kind, site=site, chosen=chosen,
                       predicted=rec.predicted, reason=reason)
        return rec

    # -- joining actuals ------------------------------------------------
    def resolve(self, rec: Optional[DecisionRecord], actual,
                verdict: Optional[str] = None) -> None:
        """Join the measured truth back onto a decision: computes the
        ``log2(predicted/actual)`` error when both sides are positive
        numbers, folds it into the per-kind accuracy aggregates, and
        emits the ``event=decision_audit`` line + trace instant."""
        if rec is None:
            return
        actual = _num(actual)
        rec.actual = actual
        pred = rec.predicted
        if pred is not None and actual is not None \
                and pred > 0 and actual > 0:
            rec.err_log2 = math.log2(pred / actual)
            rec.verdict = verdict or (
                "ok" if abs(rec.err_log2) <= _OK_LOG2 else "off")
            with self._lock:
                self.joined_counts[rec.kind] = \
                    self.joined_counts.get(rec.kind, 0) + 1
                self._acc.setdefault(rec.kind, Aggregate()).add(
                    abs(rec.err_log2))
                se = self._site_err.setdefault((rec.kind, rec.site),
                                               [0, 0.0])
                se[0] += 1
                se[1] += abs(rec.err_log2)
        else:
            rec.verdict = verdict or "unmeasured"
            with self._lock:
                self.joined_counts[rec.kind] = \
                    self.joined_counts.get(rec.kind, 0) + 1
        log = self.logger
        if log is not None and log.enabled:
            log.line(**rec.audit_rec())
        tr = self.tracer
        if tr is not None and tr.enabled:
            tr.instant("plan", rec.kind + "_audit", site=rec.site,
                       verdict=rec.verdict,
                       err_log2=(round(rec.err_log2, 3)
                                 if rec.err_log2 is not None else None))
        hook = self.audit_hook
        if hook is not None:
            # the planner's re-optimization trigger; a raising hook
            # must not break the audit join it rides on (planning is
            # perf, the join is observability — neither may take down
            # the pipeline that produced the actual)
            try:
                hook(rec)
            except Exception:
                pass

    def resolve_site(self, kind: str, site: str, actual,
                     verdict: Optional[str] = None) -> bool:
        """Join by (kind, site) for scopes that no longer hold the
        record (record_prune). Returns False when no open record
        matches — joins are best-effort by contract."""
        with self._lock:
            rec = self._open.pop((kind, site), None)
        if rec is None:
            return False
        self.resolve(rec, actual, verdict=verdict)
        return True

    # -- aggregates -----------------------------------------------------
    def accuracy(self) -> Dict[str, dict]:
        """Per-kind accuracy ledger: records, joined actuals, mean and
        stdev of |log2(predicted/actual)|."""
        with self._lock:
            out = {}
            for kind, n in sorted(self.kind_counts.items()):
                agg = self._acc.get(kind)
                out[kind] = {
                    "n": n,
                    "joined": self.joined_counts.get(kind, 0),
                    "mae_log2": round(agg.mean, 4) if agg is not None
                    and agg.count else None,
                    "stdev_log2": round(agg.stdev, 4)
                    if agg is not None and agg.count else None,
                }
            return out

    def worst_sites(self, k: int = 5) -> List[dict]:
        """Top-k sites by mean |log2 err| — where the cost model lies
        the most (json2profile's decisions lane, plan_report)."""
        with self._lock:
            rows = [{"kind": kind, "site": site, "n": n,
                     "mae_log2": round(tot / n, 4)}
                    for (kind, site), (n, tot) in self._site_err.items()
                    if n]
        rows.sort(key=lambda r: -r["mae_log2"])
        return rows[:k]

    def snapshot(self) -> List[dict]:
        """Record dicts (audit fields merged) for rendering — a copy,
        so the service dispatcher may keep recording mid-render."""
        with self._lock:
            recs = list(self.records)
        out = []
        for r in recs:
            d = r.rec()
            if r.verdict is not None:
                d["verdict"] = r.verdict
            if r.actual is not None:
                d["actual"] = r.actual
            if r.err_log2 is not None:
                d["err_log2"] = round(r.err_log2, 4)
            out.append(d)
        return out

    def summary(self) -> dict:
        """The persisted accuracy ledger (PlanStore.save_ledger)."""
        return {"version": 1,
                "decisions": sum(self.kind_counts.values()),
                "accuracy": self.accuracy(),
                "worst_sites": self.worst_sites()}

    def dump_beside(self, flight_path: Optional[str]) -> Optional[str]:
        """Archive the ledger next to a flight-recorder dump (the
        chaos sweep keeps both): ``flight-*.json`` gains a sibling
        ``decisions-*.json`` with the summary plus the ring's records.
        Best-effort like the flight dump itself."""
        if flight_path is None or not self.enabled:
            return None
        recs = self.snapshot()
        if not recs:
            return None
        d, name = os.path.split(flight_path)
        if not name.startswith("flight-"):
            return None
        path = os.path.join(d, "decisions-" + name[len("flight-"):])
        try:
            with open(path, "w") as f:
                f.write(json.dumps(self.summary(), default=str) + "\n")
                for r in recs:
                    f.write(json.dumps(r, default=str) + "\n")
        except OSError:
            return None
        return path


def _num(v) -> Optional[float]:
    """Coerce to a plain float (np scalars repr badly in JSON);
    None/NaN stay None."""
    if v is None or isinstance(v, bool):
        return None
    try:
        f = float(v)
    except (TypeError, ValueError):
        return None
    return f if math.isfinite(f) else None


# ----------------------------------------------------------------------
# guarded one-liners for the choke points (the span_of pattern)
# ----------------------------------------------------------------------

def ledger_of(mex) -> Optional[DecisionLedger]:
    """The mesh's ledger when recording is live, else None — ONE
    attribute read plus one predicate on the disabled path (the pinned
    THRILL_TPU_DECISIONS=0 contract)."""
    led = getattr(mex, "decisions", None)
    if led is not None and led.enabled:
        return led
    return None


def record_of(mex, kind: str, site: str, chosen: str,
              **kw) -> Optional[DecisionRecord]:
    led = ledger_of(mex)
    if led is None:
        return None
    return led.record(kind, site, chosen, **kw)


def resolve_of(mex, rec: Optional[DecisionRecord], actual,
               verdict: Optional[str] = None) -> None:
    if rec is None:
        return
    led = getattr(mex, "decisions", None)
    if led is not None:
        led.resolve(rec, actual, verdict=verdict)


def resolve_io_prefetch(mex, rec: Optional[DecisionRecord],
                        io_delta: dict) -> None:
    """THE audit-join formula for ``io_prefetch`` decisions, shared by
    every readahead site (em_sort merge, checkpoint/hbm restore):
    joined actual = the measured hit rate over the window's consumed
    readahead, clamped away from zero so an all-miss run resolves as a
    loud finite error; a window that never consumed readahead at all
    stays unmeasured. One definition — the planner's learned per-site
    depth grows from this signal, and the sites must not drift apart
    in what they feed it."""
    if rec is None:
        return
    from .iostats import hit_rate
    consumed = io_delta.get("prefetch_hits", 0) \
        + io_delta.get("prefetch_misses", 0)
    resolve_of(mex, rec,
               max(hit_rate(io_delta), 1e-3) if consumed else None)


# ----------------------------------------------------------------------
# the shared explain() renderer
# ----------------------------------------------------------------------

def _fmt_bytes(n) -> str:
    try:
        n = float(n)
    except (TypeError, ValueError):
        return str(n)
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024 or unit == "GiB":
            return (f"{n:.0f}{unit}" if unit == "B"
                    else f"{n:.1f}{unit}")
        n /= 1024
    return f"{n:.1f}GiB"


def _fmt_decision(d: dict) -> str:
    """One decision as an annotated line: kind, chosen-vs-rejected
    with estimated costs, the reason, and the audit verdict."""
    parts = [f"{d['kind']}: chose {d['chosen']}"]
    # prune predictions are fractions, capacity predictions row counts;
    # everything else predicts bytes
    unit = (d.get("inputs") or {}).get("unit") or "bytes"
    fmt = (lambda v: f"{float(v):.3g}") if unit != "bytes" else _fmt_bytes
    rej = d.get("rejected") or []
    if rej:
        alts = ", ".join(f"{a} est {_fmt_bytes(c)}" if _num(c)
                         is not None else str(a) for a, c in rej)
        parts.append(f"over {alts}")
    if d.get("predicted") is not None:
        parts.append(f"pred {fmt(d['predicted'])}")
    if d.get("reason"):
        parts.append(f"({d['reason']})")
    if d.get("actual") is not None:
        err = d.get("err_log2")
        audit = f"actual {fmt(d['actual'])}"
        if err is not None:
            audit += f", err x{2 ** abs(err):.2f} [{d.get('verdict')}]"
        elif d.get("verdict"):
            audit += f" [{d['verdict']}]"
        parts.append("-> " + audit)
    elif d.get("verdict"):
        parts.append(f"-> [{d['verdict']}]")
    return " ".join(parts)


def render_plan(nodes: List[dict], decisions: List[dict],
                W: Optional[int] = None, title: str = "") -> str:
    """Render the physical plan as an annotated tree.

    ``nodes``: [{"id", "label", "state", "parents": [ids]}, ...] —
    from live DIA nodes (ctx.explain / DIA.explain) or reconstructed
    from ``node_execute_start``/``node_fused`` log events
    (tools/plan_report.py). ``decisions``: record dicts as produced by
    :meth:`DecisionLedger.snapshot` (audits merged).

    Sinks render first (consumer at top, parents indented below — the
    pull direction); shared parents render once and are referenced by
    id afterwards. Decisions attach to the node whose compute recorded
    them (``dia_id``); site-less ones land in a trailing "plan-wide"
    section. Nodes in state FUSED are annotated with the stitched
    program that consumed them (the ``fusion`` decision naming their
    dia id)."""
    by_id = {n["id"]: n for n in nodes}
    ids = set(by_id)
    referenced = {p for n in nodes for p in n.get("parents", ())
                  if p in ids}
    sinks = [n for n in nodes if n["id"] not in referenced]
    # decisions by node
    per_node: Dict[int, List[dict]] = {}
    rest: List[dict] = []
    fused_names: Dict[int, str] = {}
    for d in decisions:
        if d.get("kind") == "fusion":
            for nid in (d.get("inputs") or {}).get("dia_ids") or ():
                if nid is not None:
                    fused_names.setdefault(int(nid),
                                           (d.get("inputs")
                                            or {}).get("ops", ""))
        nid = d.get("dia_id")
        if nid is not None:
            if nid in ids:
                per_node.setdefault(nid, []).append(d)
            # else: bound to a node OUTSIDE this plan (an earlier
            # pipeline on a reused Context, or outside this DIA's
            # subgraph) — dropping it keeps explain() about THIS plan
        else:
            rest.append(d)
    lines: List[str] = []
    head = title or "physical plan"
    if W:
        head += f" (W={W})"
    lines.append(head)
    seen: set = set()

    def walk(root: int) -> None:
        # explicit stack, not recursion: a long chained pipeline can
        # nest deeper than the interpreter's recursion limit
        stack: List[Tuple[int, int]] = [(root, 0)]
        while stack:
            nid, depth = stack.pop()
            pad = "  " * depth
            n = by_id.get(nid)
            if n is None:
                lines.append(f"{pad}- #{nid} (outside this plan)")
                continue
            state = n.get("state") or "?"
            tag = f"{pad}- {n.get('label', '?')}#{nid} [{state}]"
            if state == "FUSED" and nid in fused_names:
                tag += f"  ~ fused into [{fused_names[nid]}]"
            if nid in seen:
                lines.append(tag + "  (see above)")
                continue
            seen.add(nid)
            lines.append(tag)
            for d in per_node.get(nid, ()):
                lines.append(f"{pad}    . {_fmt_decision(d)}")
            for p in reversed(n.get("parents", ())):
                stack.append((p, depth + 1))

    for s in sorted(sinks, key=lambda n: n["id"], reverse=True):
        walk(s["id"])
    if rest:
        lines.append("plan-wide decisions:")
        # collapse repeats (loop iterations re-record the same site):
        # show each (kind, site, chosen) once with a xN count and the
        # LAST audit (latest truth wins)
        grouped: Dict[Tuple, List[dict]] = {}
        for d in rest:
            grouped.setdefault((d.get("kind"), d.get("site"),
                                d.get("chosen")), []).append(d)
        for key, ds in grouped.items():
            last = ds[-1]
            cnt = f"  x{len(ds)}" if len(ds) > 1 else ""
            lines.append(f"  . {_fmt_decision(last)}{cnt}")
    return "\n".join(lines)


def render_accuracy(accuracy: Dict[str, dict],
                    worst: List[dict]) -> str:
    """The audited-accuracy table (plan_report, run scripts)."""
    lines = ["decision accuracy (|log2 predicted/actual|):",
             f"  {'kind':<16} {'n':>5} {'joined':>7} {'mae':>7} "
             f"{'stdev':>7}"]
    for kind, row in sorted(accuracy.items()):
        mae = row.get("mae_log2")
        sd = row.get("stdev_log2")
        lines.append(
            f"  {kind:<16} {row.get('n', 0):>5} "
            f"{row.get('joined', 0):>7} "
            f"{mae if mae is not None else '-':>7} "
            f"{sd if sd is not None else '-':>7}")
    if worst:
        lines.append("worst-audited sites:")
        for r in worst:
            lines.append(f"  {r['kind']}@{r['site']}: "
                         f"mae {r['mae_log2']} over {r['n']} joins")
    return "\n".join(lines)
