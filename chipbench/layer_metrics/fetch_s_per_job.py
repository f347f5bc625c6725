"""Seconds copying device arrays to the host per job of the window: the
``fetch`` spans (the copy alone, after the ``wait``), counted fetches and
deferred checks alike. The window is cut from the program's own records
(``span_window.py``)."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "chipbench_span_window",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                 "span_window.py"))
span_window = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(span_window)


def read(run: dict):
    return span_window.per_job(run, "fetch_s")
