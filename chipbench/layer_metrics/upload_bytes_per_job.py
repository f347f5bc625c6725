"""Bytes handed to ``jax.device_put`` per job of the window: the
``bytes`` of the ``upload`` spans (the host arrays' ``nbytes``, padding
included). The window is cut from the program's own records
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
    return span_window.per_job(run, "upload_bytes")
