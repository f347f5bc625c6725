"""Host seconds per job of the window that the iteration layer costs
outside upload, dispatch call, wait and fetch: the self time of the
``loop`` spans (``capture``, ``replay``, ``rebind``; ``api/loop.py``),
which ``host_plan_s_per_job`` leaves out. The window is cut from the
program's own records (``loop_window.py``)."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "chipbench_loop_window",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                 "loop_window.py"))
loop_window = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(loop_window)


def read(run: dict):
    totals = loop_window.loops(run)
    return None if totals is None else totals["self_s"] / run["jobs"]
