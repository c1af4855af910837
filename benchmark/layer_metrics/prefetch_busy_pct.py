"""Time inside the worker's ``fed.cohort.prefetch`` spans over the traced
window: how much of a round the prefetcher needs to prepare the next cohort
(gather, staging, H2D enqueue). At 100 the round waits for the worker. Cells
that stream their cohorts from a host store.
"""

import os
import sys

BENCHMARK = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCHMARK not in sys.path:
    sys.path.insert(0, BENCHMARK)
import reduce_spans as rs  # noqa: E402  (benchmark/reduce_spans.py)

PREFETCH = "fed.cohort.prefetch"
META = {"layer": "round loop", "unit": "%", "moves": "rounds_per_s"}


def applies(cell: dict) -> bool:
    return rs.host_store(cell)


def read(summary: dict):
    r = rs.traced()
    if not r or PREFETCH not in r["spans"] or not r["window_ns"]:
        return None
    return 100.0 * r["spans"][PREFETCH]["total_ns"] / r["window_ns"]
