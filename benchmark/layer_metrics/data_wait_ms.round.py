"""Main-thread time a traced round inside ``fed.cohort.wait``: the join on the
cohort's prefetch and, on a miss, the synchronous gather and put.
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
    return rs.per_round(lambda r: r["main_ns"].get(rs.WAIT))
