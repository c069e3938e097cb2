"""Test-suite bootstrap: puts src/ on sys.path so `python -m pytest` works
without PYTHONPATH (pyproject's pythonpath ini handles pytest>=7; this
covers direct runs)."""
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SRC = os.path.join(_ROOT, "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)
