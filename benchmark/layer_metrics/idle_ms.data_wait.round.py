"""Idle time of the first device a traced round under ``fed.cohort.wait``: the
round's cohort was not on the device yet.
"""

import os
import sys

BENCHMARK = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCHMARK not in sys.path:
    sys.path.insert(0, BENCHMARK)
import reduce_spans as rs  # noqa: E402  (benchmark/reduce_spans.py)

META = {"layer": "round loop", "unit": "ms", "moves": "rounds_per_s"}


def applies(cell: dict) -> bool:
    return rs.host_store(cell)


def read(summary: dict):
    return rs.per_round(lambda r: rs.idle_ns(r, inside=(rs.WAIT,)))
