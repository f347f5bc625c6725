"""Seconds of backend compiles (or loads from the compile cache) under
the window's dispatches: the ``compile`` spans, a total and not a mean;
0.0 in a warm window. The programs' labels go to standard error. The
window is cut from the program's own records (``span_window.py``)."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "chipbench_span_window",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                 "span_window.py"))
span_window = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(span_window)


def read(run: dict):
    p = span_window.phases(run)
    return None if p is None else p["compile_s"]
