"""Idle time of the first device a traced round under ``fed.round`` but under
neither of its waits: the host was working (sampling, dispatching) and the
device had nothing to run.
"""

import os
import sys

BENCHMARK = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCHMARK not in sys.path:
    sys.path.insert(0, BENCHMARK)
import reduce_spans as rs  # noqa: E402  (benchmark/reduce_spans.py)

META = {"layer": "round loop", "unit": "ms", "moves": "rounds_per_s"}


def applies(cell: dict) -> bool:
    return True


def read(summary: dict):
    return rs.per_round(lambda r: rs.idle_ns(
        r, inside=(rs.ROUND,), outside=(rs.WAIT, rs.SYNC)))
