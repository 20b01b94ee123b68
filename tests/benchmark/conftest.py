"""The benchmark's tests import its harness the way benchmarks/run.py
does: with benchmarks/ and the repo root on the path."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "benchmarks"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)
