"""Repo-root on sys.path for direct CLI runs
(`python examples/x.py`)."""

import os
import sys

_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _root not in sys.path:
    sys.path.insert(0, _root)
