"""``ReduceToIndex`` index plans computed per job: the sum of
``index_plans`` over the ``dispatch`` and ``loop`` / ``replay`` spans of
the window's jobs, over the jobs. A plan is a stable argsort of the
fold's index, a histogram and a cumulative sum (``core/segmented.py
sorted_fold_plan``); a loop whose index does not depend on its carry
computes it once ahead of the iterations, one whose index does computes
it in every iteration. Each plan is counted once: a ``dispatch`` span
carries the plans its program computes in place, and a whole-loop
dispatch carries 0 there and its plans on the ``replay`` span around it
(``thrill_tpu/common/trace.py``'s inventory). The window is cut from the
program's own records (``loop_window.py``); ``None``, never 0, where no
span of the window carries the field (a parent commit)."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "chipbench_loop_window",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                 "loop_window.py"))
loop_window = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(loop_window)
span_window = loop_window.span_window


def read(run: dict):
    # the loop totals and the sum check reach standard error: no other
    # reader of a k-means cell asks for them
    loop_window.loops(run)
    records = span_window.ring_records()
    if records is None or not run.get("jobs"):
        return None
    trace = run.get("trace")
    jobs = span_window.window_jobs(records, int(run["jobs"]),
                                   trace["window_s"] if trace else None)
    if jobs is None:
        return None
    plans = [r["index_plans"] for job in jobs for r in job
             if "index_plans" in r
             and (r["cat"] == "dispatch"
                  or (r["cat"] == "loop" and r["name"] == "replay"))]
    return sum(plans) / len(jobs) if plans else None
