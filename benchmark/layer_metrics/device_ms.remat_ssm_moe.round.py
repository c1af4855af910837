"""``device_ms.remat.round``'s reading in a cell whose configuration lists its
model's scopes under ``scopes_ssm_moe``: self time of the first device's
operations a traced round in the forward pass computed AGAIN for the backward
(``nn.remat`` block by block): a path that holds ``rematted_computation``
(``reduce_booked.py``). Work the yardstick of ``mfu.client_step`` does not
count.
"""

import os
import sys

BENCHMARK = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCHMARK not in sys.path:
    sys.path.insert(0, BENCHMARK)
import reduce_booked as rb  # noqa: E402  (benchmark/reduce_booked.py)
import reduce_scopes as rsc  # noqa: E402  (benchmark/reduce_scopes.py)

META = {"layer": "client step", "unit": "ms", "moves": "rounds_per_s"}


def applies(cell: dict) -> bool:
    return bool(rsc._config_of(cell).get("scopes_ssm_moe"))


def read(summary: dict):
    return rb.per_round(lambda r: r["pass_ns"].get(rb.REMAT))
