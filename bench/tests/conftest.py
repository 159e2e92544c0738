"""Puts the repository root and ``src`` on the path for the benchmark's
tests (run them by path: ``pytest bench/tests``)."""
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (REPO, os.path.join(REPO, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
