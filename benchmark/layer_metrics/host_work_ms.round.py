"""Main-thread time a traced round inside ``fed.round`` less its two waits,
``fed.cohort.wait`` (the cohort) and ``fed.round.loss_fetch`` (the device):
what the host itself does in a round (sample, gather or its dispatch, the
fused step's dispatch, next-round prefetch start, carry commit).
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
    def work(r):
        if rs.ROUND not in r["main_ns"]:
            return None
        return (r["main_ns"][rs.ROUND] - r["main_ns"].get(rs.WAIT, 0)
                - r["main_ns"].get(rs.SYNC, 0))

    return rs.per_round(work)
