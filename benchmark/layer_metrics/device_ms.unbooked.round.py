"""Self time of the first device's LEAF operations a traced round whose
``op_name`` path names no ``fed.*`` scope but, at most, a bare
``fed.local_train``: what no phase and no scope says the purpose of
(``reduce_booked.py``; loop containers are ``device_ms.loop_self.round``'s).
The traced run's stderr ranks what is left by why (no metadata from XLA,
transforms only, bare) and by kind.
"""

import os
import sys

BENCHMARK = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCHMARK not in sys.path:
    sys.path.insert(0, BENCHMARK)
import reduce_booked as rb  # noqa: E402  (benchmark/reduce_booked.py)

META = {"layer": "client step", "unit": "ms", "moves": "rounds_per_s"}


def applies(cell: dict) -> bool:
    return rb.names_model_scopes(cell)


def read(summary: dict):
    return rb.per_round(lambda r: r["unbooked_ns"])
