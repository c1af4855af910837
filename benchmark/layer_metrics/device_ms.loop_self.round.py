"""Self time of the first device's loop containers a traced round (``while``,
``conditional``, ``call``: events of the ``XLA Ops`` line that span their
body's operations): the space BETWEEN a loop body's operations, which
``device_ms.round`` and ``device_idle_pct`` count as busy
(``reduce_booked.py``).
"""

import os
import sys

BENCHMARK = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCHMARK not in sys.path:
    sys.path.insert(0, BENCHMARK)
import reduce_booked as rb  # noqa: E402  (benchmark/reduce_booked.py)

META = {"layer": "device", "unit": "ms", "moves": "rounds_per_s"}


def applies(cell: dict) -> bool:
    return True


def read(summary: dict):
    return rb.per_round(lambda r: sum(r["container_ns"].values()))
