"""Self time of the first device's operations a traced round under the
``fed.aggregate`` scope: the weighted mean over the clients, with its
collectives, and the server update.
"""

import os
import sys

BENCHMARK = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCHMARK not in sys.path:
    sys.path.insert(0, BENCHMARK)
import reduce_spans as rs  # noqa: E402  (benchmark/reduce_spans.py)

META = {"layer": "client parallelism", "unit": "ms", "moves": "rounds_per_s"}


def applies(cell: dict) -> bool:
    return True


def read(summary: dict):
    return rs.per_round(lambda r: rs.phase_ns(r, "fed.aggregate"))
