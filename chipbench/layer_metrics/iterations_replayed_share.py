"""Of the iterations the window's jobs ran, the share that ran off a
tape: ``replay`` spans, each one iteration or the ``fori_iters`` of one
whole-loop dispatch, over those plus the ``capture`` spans. ``None``,
never 0, where the window holds no ``loop`` span. The window is cut from
the program's own records (``loop_window.py``)."""

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
    if totals is None or not totals["iterations"]:
        return None
    return 100.0 * totals["iterations_replayed"] / totals["iterations"]
