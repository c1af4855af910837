"""Idle time of the first device a traced round outside every ``fed.round``:
the caller's loop and its fence. With the other three ``idle_ms.*`` it adds
up to ``host_gap_ms.round``.
"""

import os
import sys

BENCHMARK = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCHMARK not in sys.path:
    sys.path.insert(0, BENCHMARK)
import reduce_spans as rs  # noqa: E402  (benchmark/reduce_spans.py)

META = {"layer": "entry", "unit": "ms", "moves": "rounds_per_s"}


def applies(cell: dict) -> bool:
    return True


def read(summary: dict):
    return rs.per_round(lambda r: rs.idle_ns(r, outside=(rs.ROUND,)))
