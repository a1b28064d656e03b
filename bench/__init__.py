"""Chip benchmark of the federated round (see ``BENCHMARK.json`` at the root).

Importing the package puts the repository's ``src/`` on ``sys.path`` so the
harness can import the program under test; in a directory that holds only the
benchmark, that import fails and no run starts.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SRC = os.path.join(ROOT, "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)
