"""Seconds per job in which an upload's bytes were on their way to the
device: the union of the job's ``transfer`` spans (``common/trace.py
DeviceWatcher``: from the put's start to its buffer being ready, a child
of the ``upload`` span). ``upload_s_per_job`` beside it reads the call,
which returns before the bytes are there. The window is cut from the
program's own records (``span_window.window_jobs``: a transfer joins its
job through its ``upload`` parent). ``None``, never 0, where the records
are missing (a parent commit without ``transfer`` spans, the Tracer off,
a wrapped ring). On standard error: GB/s by leaf (dtype and shape), each
leaf's bytes over its transfers' seconds, the evidence for whether the
host's re-tiling or the DMA sets the rate."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "chipbench_span_window",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                 "span_window.py"))
span_window = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(span_window)


def union_s(intervals) -> float:
    """Seconds covered by [start, end) intervals."""
    total, reach = 0.0, float("-inf")
    for start, end in sorted(intervals):
        if end > reach:
            total += end - max(start, reach)
            reach = end
    return total


def span_of(rec) -> tuple:
    return rec["t0_s"], rec["t0_s"] + rec["dur_us"] / 1e6


def window(run: dict):
    """The window's jobs (``span_window.window_jobs``), or None. A
    ``transfer`` or ``device`` record is given to the job of its parent
    ``upload`` / ``dispatch`` span: ``window_jobs`` cuts by start, and a
    record the watcher closed late may start after the next job's
    first span."""
    records = span_window.ring_records()
    if records is None or not run.get("jobs"):
        return None
    trace = run.get("trace")
    jobs = span_window.window_jobs(records, int(run["jobs"]),
                                   trace["window_s"] if trace else None)
    if jobs is None:
        return None
    watched = ("transfer", "device")
    by_parent = {}
    for r in records:
        if r["cat"] in watched and r.get("kind") != "instant":
            by_parent.setdefault(r.get("parent"), []).append(r)
    out = []
    for job in jobs:
        job = [r for r in job if r["cat"] not in watched]
        out.append(job + [c for r in job for c in by_parent.get(r["span"], ())])
    return out


def read(run: dict):
    jobs = window(run)
    if jobs is None:
        return None
    transfers = [[r for r in job if r["cat"] == "transfer"] for job in jobs]
    if not any(transfers):
        return None
    by_leaf = {}
    for rec in (r for job in transfers for r in job):
        leaf = f"{rec.get('dtype')}{rec.get('shape')}"
        nbytes, seconds = by_leaf.get(leaf, (0, 0.0))
        by_leaf[leaf] = (nbytes + rec.get("bytes", 0),
                         seconds + rec["dur_us"] / 1e6)
    n = len(jobs)
    span_window.say("transfer rate by leaf (GB/s; bytes and seconds per "
                    "job): " + " ".join(
                        f"{leaf}={b / s / 1e9 if s else 0.0:.3f}"
                        f"({b / n:.0f}B,{s / n:.6f}s)"
                        for leaf, (b, s) in sorted(by_leaf.items())))
    span_window.say("transfer / upload spans per job: " + " ".join(
        f"{len(t)}/{sum(r['cat'] == 'upload' for r in job)}"
        for t, job in zip(transfers, jobs)))
    return sum(union_s(span_of(r) for r in job) for job in transfers) / n
