"""Host seconds per job of the window that are neither upload, dispatch
call, wait nor fetch: the self time of the ``stage``, ``fusion`` and
``exchange`` spans (planning, fusion, Python; ``Distribute``'s host
copies before ``put`` are its stage's self time). The window is cut from
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
    return span_window.per_job(run, "host_plan_s")
