"""Share of the traced rounds' ``fed.cohort.wait`` spans with no main-thread
``fed.store.gather`` inside: the cohort the prefetcher prepared was the one
the round asked for.
"""

import os
import sys

BENCHMARK = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCHMARK not in sys.path:
    sys.path.insert(0, BENCHMARK)
import reduce_spans as rs  # noqa: E402  (benchmark/reduce_spans.py)

META = {"layer": "round loop", "unit": "%", "moves": "rounds_per_s"}


def applies(cell: dict) -> bool:
    return rs.host_store(cell)


def read(summary: dict):
    r = rs.traced()
    if not r or not r["waits"]:
        return None
    return 100.0 * (r["waits"] - r["misses"]) / r["waits"]
