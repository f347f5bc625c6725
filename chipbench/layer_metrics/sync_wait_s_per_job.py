"""Seconds the program's thread is blocked on the device per job of the
window: the ``wait`` spans (``block_until_ready`` before a fetch's copy,
``MeshExec._fetch_raw``). A wait the caller makes outside the program is
not in it. The window is cut from the program's own records
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
    return span_window.per_job(run, "sync_wait_s")
