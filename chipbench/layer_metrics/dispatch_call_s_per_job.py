"""Seconds the program's thread spends inside device dispatch calls per
job of the window: the ``dispatch`` spans (``_CountedJit.__call__``) less
their ``compile`` children. The call returns once the program is
enqueued, so this is launch cost, not device time. The window is cut from
the program's own records (``span_window.py``)."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "chipbench_span_window",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                 "span_window.py"))
span_window = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(span_window)


def read(run: dict):
    return span_window.per_job(run, "dispatch_call_s")
