"""Self time of the first device's operations a traced round under the
``fed.gather`` scope: gathering the cohort from the resident federation. 0
where the program names its phases and this one runs nothing on the device
(a host store).
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
    return rs.per_round(lambda r: rs.phase_ns(r, "fed.gather"))
