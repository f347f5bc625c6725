"""Seconds per job between one pull's last result and the next pull's
first dispatch: summed over a job's consecutive pulls, from the end of
pull k's last ``fetch`` or ``wait`` span to the start of pull k+1's
first ``dispatch`` span. That is host time in which the device has
nothing queued, because the next round is not planned before the read
that ends this one has come back: the price of a loop whose end depends
on its data and which the client drives (``api/loop.py`` cannot take
it). A pull is a root ``stage`` span (no span above it) with the records
under it; the window is cut from the program's own records
(``span_window.window_jobs``). A pull without a ``fetch`` or ``wait``
(the result leaves through device arrays) or a successor without a
``dispatch`` adds nothing. ``None``, never 0, where the records are
missing (a parent commit, the Tracer off, a wrapped ring) or no job of
the window has two pulls. Each job's gaps are said on standard error:
their number must be the same in every job. Without a device trace (a
rehearsal) ``span_window`` keeps only the first root of the window's
last job, so that job is left out here."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "chipbench_span_window",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                 "span_window.py"))
span_window = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(span_window)


def window_pulls(run: dict):
    """Per job of the window, its pulls in time order: each the list of
    the records under one root ``stage``. None without sound records."""
    records = span_window.ring_records()
    if records is None or not run.get("jobs"):
        return None
    trace = run.get("trace")
    jobs = span_window.window_jobs(records, int(run["jobs"]),
                                   trace["window_s"] if trace else None)
    if jobs is None:
        return None
    if not trace:
        jobs = jobs[:-1]
    out = []
    for recs in jobs:
        ids = {r["span"]: r for r in recs}

        def root(r):
            while r.get("parent") in ids:
                r = ids[r["parent"]]
            return r["span"]

        under = {}
        for r in recs:
            under.setdefault(root(r), []).append(r)
        out.append([under[s] for s in sorted(
            (s for s in under if ids[s]["cat"] == "stage"),
            key=lambda s: ids[s]["t0_s"])])
    return out


def gaps(job_pulls) -> list:
    """The gaps of one job, whose pulls come in time order."""
    out = []
    for before, after in zip(job_pulls, job_pulls[1:]):
        ends = [r["t0_s"] + r["dur_us"] / 1e6 for r in before
                if r["cat"] in ("fetch", "wait")]
        starts = [r["t0_s"] for r in after if r["cat"] == "dispatch"]
        if ends and starts:
            out.append(max(0.0, min(starts) - max(ends)))
    return out


def read(run: dict):
    pulls = window_pulls(run)
    if not pulls or max(len(job) for job in pulls) <= 1:
        return None
    per_job = [gaps(job) for job in pulls]
    span_window.say("replan gaps per job (s): " + " | ".join(
        " ".join(f"{g:.6f}" for g in job) for job in per_job))
    return sum(sum(job) for job in per_job) / len(per_job)
