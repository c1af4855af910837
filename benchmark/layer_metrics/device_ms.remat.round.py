"""Self time of the first device's operations a traced round in the forward
pass computed AGAIN for the backward (``nn.remat`` layer by layer,
``jax.checkpoint`` on the expert arms): a path that holds
``rematted_computation`` (``reduce_booked.py``). Work the yardstick of
``mfu.client_step`` does not count. The cells whose model names scopes.
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
    return rb.per_round(lambda r: r["pass_ns"].get(rb.REMAT))
