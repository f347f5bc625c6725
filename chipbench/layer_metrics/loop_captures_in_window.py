"""``loop`` spans named ``capture`` in the window's jobs, a total and not
a mean: iterations that ran through the pull recursion and the planner
(captured or plain). 0 where every job of the window took over the tape
the warm-up job captured. The window is cut from the program's own
records (``loop_window.py``)."""

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
    return None if totals is None else totals["captures"]
