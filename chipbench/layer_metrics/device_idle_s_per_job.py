"""Seconds per job in which no program of the job was running on the
device, as the program's own records see it: the job's seconds on the
harness's clock (``run["job_seconds"]``, from just before the input is
handed over to the result ready: the window the device trace's job spans
cut) less what its ``device`` spans cover (``common/trace.py
DeviceWatcher``: from a program's effective start to its outputs being
ready, a child of its ``dispatch`` span). A host-side view: it cannot see
the device's own gaps inside a program or between two queued programs,
and a record's end is late by the GIL while the host runs Python, so it
reads below ``device_idle_share`` x ``window_s`` / jobs where a job is
many short programs (PERF.md section 5 has both, cell by cell). The
window is cut from the program's own records
(``span_window.window_jobs``). ``None``, never 0, where the records are
missing (a parent commit without ``device`` spans, the Tracer off, a
wrapped ring).

On standard error: that time split by cause (``transfer`` where one is in
flight; else the innermost host span open then on the dispatching thread,
``cat:name``: a ``stage`` / ``fusion`` / ``exchange`` / ``loop`` span's
self time, a ``dispatch``, ``wait``, ``fetch``, ``upload``, ``compile``;
``caller`` where no span of the program is open, the job's seconds
outside its first record's start and last record's end included), the
device seconds per job by program label and their total (the twin of
``device_busy_ms_per_job``), and the counts of ``device`` against
``dispatch`` spans in each job."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "chipbench_transfer_s_per_job",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "transfer_s_per_job.py"))
transfers = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(transfers)
span_window = transfers.span_window
span_of = transfers.span_of


def idle_by_cause(job, seconds=None) -> dict:
    """One job's idle seconds, by cause; ``seconds``, the job's own on
    the harness's clock, adds what lies outside its records to
    ``caller``."""
    lo = min(r["t0_s"] for r in job)
    hi = max(span_of(r)[1] for r in job)
    busy = [span_of(r) for r in job if r["cat"] == "device"]
    moving = [span_of(r) for r in job if r["cat"] == "transfer"]
    host = [span_of(r) + (f"{r['cat']}:{r['name']}",) for r in job
            if r["cat"] not in ("device", "transfer")]
    cuts = sorted({lo, hi} | {x for s, e, *_ in busy + moving + host
                              for x in (s, e) if lo < x < hi})
    out = {}
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        if any(s <= mid < e for s, e in busy):
            continue
        if any(s <= mid < e for s, e in moving):
            cause = "transfer"
        else:
            # the innermost open span: the latest to start, the shortest
            # of those that started together
            cover = [h for h in host if h[0] <= mid < h[1]]
            cause = max(cover, key=lambda h: (h[0], -h[1]))[2] \
                if cover else "caller"
        out[cause] = out.get(cause, 0.0) + (b - a)
    if seconds is not None and seconds > hi - lo:
        out["caller"] = out.get("caller", 0.0) + seconds - (hi - lo)
    return out


def read(run: dict):
    jobs = transfers.window(run)
    if jobs is None or not any(r["cat"] == "device" for job in jobs
                               for r in job):
        return None
    n = len(jobs)
    seconds = run.get("job_seconds") or []
    if len(seconds) != n:
        seconds = [None] * n
    causes, by_label = {}, {}
    for job, s_job in zip(jobs, seconds):
        for cause, s in idle_by_cause(job, s_job).items():
            causes[cause] = causes.get(cause, 0.0) + s
        for r in job:
            if r["cat"] == "device":
                by_label[r["name"]] = by_label.get(r["name"], 0.0) \
                    + r["dur_us"] / 1e6
    span_window.say("device idle seconds per job by cause: " + " ".join(
        f"{c}={s / n:.6f}" for c, s in sorted(
            causes.items(), key=lambda kv: -kv[1]) if s / n >= 5e-7))
    span_window.say("device seconds per job by program: " + " ".join(
        f"{k}={s / n:.6f}" for k, s in sorted(by_label.items()))
        + f" total={sum(by_label.values()) / n:.6f}")
    span_window.say("device / dispatch spans per job: " + " ".join(
        f"{sum(r['cat'] == 'device' for r in job)}/"
        f"{sum(r['cat'] == 'dispatch' for r in job)}" for job in jobs)
        + "; closed at a consumer's ready (donated): "
        + str(sum(bool(r.get("donated")) for job in jobs for r in job
                  if r["cat"] == "device")))
    return sum(causes.values()) / n
