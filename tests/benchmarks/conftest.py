"""The benchmark's tests import its modules as the command does: with
`benchmarks/` (for `harness`, `reference`, `run`) on the path."""

import os
import sys

BENCH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "benchmarks",
)
for p in (BENCH, os.path.dirname(os.path.abspath(__file__))):
    if p not in sys.path:
        sys.path.insert(0, p)
