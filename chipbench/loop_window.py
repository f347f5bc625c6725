"""The ``loop`` spans of the traced window's jobs, for the per-layer
readers of the iteration layer (``thrill_tpu/api/loop.py``).

The window is cut by ``span_window.window_jobs`` (its docstring is the
rule: one pipeline per job, the warm-up job and what follows the window
left out). ``Iterate`` runs under a root ``stage`` span named ``Iterate``;
under it one ``loop`` span per captured or plain iteration (``capture``,
one iteration each), per replay (``replay``: one iteration, or
``fori_iters`` of them inside one whole-loop dispatch; none where it
carries ``error`` and fell back) and per call that took over a kept tape
(``rebind``: the prologue, no iteration). A ``loop`` span's self time is
its duration less its direct children's (the dispatches, waits, fetches
and stages of an iteration): the host seconds the iteration layer costs
beyond the six phases of ``span_window``, which leave ``loop`` out.

``loops`` returns ``None``, never a guess or a 0: where ``span_window``
finds no sound window (a parent commit without these spans, the Tracer
off, a wrapped ring, too few pipelines) and where the window's jobs hold
no ``loop`` span at all (a job that does not iterate).
"""

from __future__ import annotations

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "chipbench_span_window",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "span_window.py"))
span_window = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(span_window)

CACHE_KEY = "_loop_window_loops"


def sum_loops(job_records):
    """Totals over the given jobs' records, or None without a ``loop``
    span."""
    recs = [r for job in job_records for r in job]
    loops_ = [r for r in recs if r["cat"] == "loop"]
    if not loops_:
        return None
    ids = {r["span"] for r in loops_}
    children_s = sum(r["dur_us"] / 1e6 for r in recs
                     if r.get("parent") in ids)
    replayed = sum(int(r.get("fori_iters", 1)) for r in loops_
                   if r["name"] == "replay" and "error" not in r)
    captures = sum(r["name"] == "capture" for r in loops_)
    return {
        "self_s": sum(r["dur_us"] / 1e6 for r in loops_) - children_s,
        "captures": captures,
        "rebinds": sum(r["name"] == "rebind" for r in loops_),
        "iterations_replayed": replayed,
        "iterations": replayed + captures,
    }


def loops(run: dict):
    """The window's totals, computed once per run and kept on ``run``."""
    if CACHE_KEY in run:
        return run[CACHE_KEY]
    run[CACHE_KEY] = None
    records = span_window.ring_records()
    if records is None or not run.get("jobs"):
        return None
    trace = run.get("trace")
    jobs = span_window.window_jobs(records, int(run["jobs"]),
                                   trace["window_s"] if trace else None)
    if jobs is None:
        return None
    totals = run[CACHE_KEY] = sum_loops(jobs)
    if totals is not None:
        span_window.say(
            f"loop spans over {len(jobs)} jobs (program spans): "
            + " ".join(f"{k}={v:.6f}" if isinstance(v, float)
                       else f"{k}={v}" for k, v in totals.items()))
        # the six host phases of the same window (printed by
        # span_window) and whether, with the loop's self time, they
        # account for the root stages
        p = span_window.phases(run)
        if p is not None:
            six = sum(p[k] for k in ("upload_s", "dispatch_call_s",
                                     "sync_wait_s", "fetch_s",
                                     "host_plan_s", "compile_s"))
            span_window.say(
                f"six phases + loop self seconds per job: "
                f"sum={(six + totals['self_s']) / len(jobs):.6f} "
                f"root_stage_s={p['root_stage_s'] / len(jobs):.6f}")
    return totals
