"""Puts the checkout's root and ``src/`` on ``sys.path`` so the benchmark's
tests can import ``bench`` and the program.  Every test file here imports it
first.  (A ``conftest.py`` here would shadow ``tests/conftest.py``, which
other test files import by name.)"""
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
