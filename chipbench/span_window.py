"""The traced window, cut from the program's own span records, for the
per-layer readers whose ``source`` is ``program_span``.

The harness hands a reader ``run`` (README.md), whose ``stats`` holds only
some counter deltas and no span. So these readers take the finished run's
records from the program itself: ``thrill_tpu.common.trace.latest()`` is
the Tracer of the one ``Run()`` the harness made, its ``ring`` the
records, each with ``cat``, ``name``, ``span``, ``parent``, ``t0_s`` (the
start on ``time.perf_counter()``, the clock of the harness's job spans)
and ``dur_us``. Of ``run`` they use ``jobs`` (the window's completed
jobs) and ``trace["window_s"]`` (the traced window's seconds, where there
is a device trace).

**The rule.** A ``stage`` span with no parent is the root of everything
one pull does and carries ``pipe``, the identifier of its pipeline (each
job's ``Distribute`` starts a new one). Pipelines are taken in the order
of their first span: the first is the warm-up job, the next ``run["jobs"]``
are the window. A job's spans are those under its pipeline's roots that
start before the next pipeline's first span. For the window's last job
the limit is the window's first span start + ``window_s`` + the 20 ms by
which ``trace_reduce`` lets its one anchor be off; without a device trace
(a rehearsal) it is that pipeline's first root alone. So the warm-up job
and the result fetches the harness makes after the window are left out.

``phases`` returns ``None``, never a guess: where the program has no
``latest()`` (a parent commit without these spans), the Tracer is off
(``THRILL_TPU_TRACE=0``), its ring has wrapped (records ever written
above its capacity), or fewer than ``run["jobs"]`` + 1 pipelines are found.

The six times partition a root stage: ``upload``, ``dispatch`` (less its
``compile`` children), ``wait`` and ``fetch`` are leaves, and what is left
of every ``stage``, ``fusion`` and ``exchange`` span once its direct
children are taken out is ``host_plan``. A span of another category
under a stage would be in none of them, and the sum printed beside the
roots' seconds would show it.
"""

from __future__ import annotations

import sys

ANCHOR_SLACK_S = 0.020      # trace_reduce.ANCHOR_SLACK_NS
PLAN_CATS = ("stage", "fusion", "exchange")
CACHE_KEY = "_span_window_phases"


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def ring_records():
    """The finished run's span records, or None (see the module's
    docstring for when)."""
    try:
        from thrill_tpu.common import trace
    except ImportError:
        return None
    latest = getattr(trace, "latest", None)
    tracer = latest() if latest is not None else None
    if tracer is None or not tracer.enabled or tracer.wrapped:
        return None
    return list(tracer.ring)


def window_jobs(records, jobs: int, window_s=None):
    """The window's jobs, each the list of its span records; None where
    fewer than ``jobs`` + 1 pipelines are found."""
    spans = {r["span"]: r for r in records
             if r.get("kind") != "instant" and "t0_s" in r}
    root_of = {}

    def root(rec):
        trail = []
        while rec["span"] not in root_of:
            trail.append(rec["span"])
            parent = spans.get(rec.get("parent"))
            if parent is None:
                root_of[rec["span"]] = rec
            else:
                rec = parent
        top = root_of[rec["span"]]
        for span in trail:
            root_of[span] = top
        return top

    by_pipe = {}        # pipe -> its records, roots included
    for rec in spans.values():
        top = root(rec)
        if top["cat"] == "stage" and "pipe" in top:
            by_pipe.setdefault(top["pipe"], []).append(rec)
    first = {pipe: min(r["t0_s"] for r in recs)
             for pipe, recs in by_pipe.items()}
    order = sorted(first, key=first.get)
    if jobs < 1 or len(order) < jobs + 1:
        return None
    out = []
    for k in range(1, jobs + 1):
        recs = by_pipe[order[k]]
        if k < jobs:
            limit = first[order[k + 1]]
            recs = [r for r in recs if r["t0_s"] < limit]
        elif window_s is not None:
            limit = first[order[1]] + window_s + ANCHOR_SLACK_S
            recs = [r for r in recs if r["t0_s"] < limit]
        else:
            roots = sorted((r for r in recs if root_of[r["span"]] is r),
                           key=lambda r: r["t0_s"])
            recs = [r for r in recs if root_of[r["span"]] is roots[0]]
        out.append(recs)
    return out


def sum_phases(job_records) -> dict:
    """Totals over the given jobs' records (seconds, bytes, counts)."""
    recs = [r for job in job_records for r in job]
    ids = {r["span"]: r for r in recs}
    children_s = {}
    for r in recs:
        if r.get("parent") in ids:
            children_s[r["parent"]] = children_s.get(r["parent"], 0.0) \
                + r["dur_us"] / 1e6

    def seconds(cat):
        return sum((r["dur_us"] / 1e6 for r in recs if r["cat"] == cat),
                   0.0)

    compiled_in_dispatch = sum(
        (r["dur_us"] / 1e6 for r in recs if r["cat"] == "compile"
         and ids.get(r.get("parent"), {}).get("cat") == "dispatch"), 0.0)
    self_by_stage = {}
    host_plan = 0.0
    for r in recs:
        if r["cat"] in PLAN_CATS:
            own = r["dur_us"] / 1e6 - children_s.get(r["span"], 0.0)
            host_plan += own
            label = f"{r['cat']}:{r['name']}"
            self_by_stage[label] = self_by_stage.get(label, 0.0) + own
    return {
        "upload_s": seconds("upload"),
        "upload_bytes": sum(r.get("bytes", 0) for r in recs
                            if r["cat"] == "upload"),
        "dispatch_call_s": seconds("dispatch") - compiled_in_dispatch,
        "sync_wait_s": seconds("wait"),
        "fetch_s": seconds("fetch"),
        "host_plan_s": host_plan,
        "compile_s": seconds("compile"),
        "compiled": sorted(r["name"] for r in recs
                           if r["cat"] == "compile"),
        "dispatch_spans": sum(r["cat"] == "dispatch" for r in recs),
        "fetch_spans": sum(r["cat"] == "fetch" and r["name"] == "fetch"
                           for r in recs),
        "root_stage_s": sum(r["dur_us"] / 1e6 for r in recs
                            if r["cat"] == "stage"
                            and r.get("parent") not in ids),
        "self_s_by_span": self_by_stage,
    }


def phases(run: dict):
    """The window's totals, computed once per run and kept on ``run``;
    None where there is nothing sound to read."""
    if CACHE_KEY in run:
        return run[CACHE_KEY]
    run[CACHE_KEY] = None
    records = ring_records()
    if records is None or not run.get("jobs"):
        return None
    trace = run.get("trace")
    jobs = window_jobs(records, int(run["jobs"]),
                       trace["window_s"] if trace else None)
    if jobs is None:
        return None
    p = run[CACHE_KEY] = sum_phases(jobs)
    n = len(jobs)
    six = ("upload_s", "dispatch_call_s", "sync_wait_s", "fetch_s",
           "host_plan_s", "compile_s")
    say(f"host phases per job over {n} jobs (program spans): " + " ".join(
        f"{k}={p[k] / n:.6f}" for k in six)
        + f" sum={sum(p[k] for k in six) / n:.6f}"
        f" root_stage_s={p['root_stage_s'] / n:.6f}"
        f" upload_bytes={p['upload_bytes'] / n:.0f}"
        f" dispatch_spans={p['dispatch_spans'] / n:g}"
        f" fetch_spans={p['fetch_spans'] / n:g}")
    say("self seconds per job by span: " + " ".join(
        f"{k}={v / n:.6f}" for k, v in sorted(p["self_s_by_span"].items())))
    say(f"compile spans inside the window: {len(p['compiled'])}"
        + (f" ({', '.join(p['compiled'])})" if p["compiled"] else ""))
    return p


def per_job(run: dict, key: str):
    p = phases(run)
    return None if p is None else p[key] / run["jobs"]
