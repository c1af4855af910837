"""Self time of the first device's operations a traced round whose innermost
``fed.*`` name is ``fed.step.update`` (``trainer/local.py``): the client
optimizer, the apply and the all-masked step's select over the parameters
and the optimizer state, every local step (``reduce_booked.py``).
"""

import os
import sys

BENCHMARK = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCHMARK not in sys.path:
    sys.path.insert(0, BENCHMARK)
import reduce_booked as rb  # noqa: E402  (benchmark/reduce_booked.py)

SCOPE = "fed.step.update"
META = {"layer": "client step", "unit": "ms", "moves": "rounds_per_s"}


def applies(cell: dict) -> bool:
    return True


def read(summary: dict):
    return rb.innermost_ms(SCOPE, family="fed.step.")
