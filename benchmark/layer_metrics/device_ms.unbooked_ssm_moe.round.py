"""``device_ms.unbooked.round``'s reading in a cell whose configuration lists
its model's scopes under ``scopes_ssm_moe``: self time of the first device's
LEAF operations a traced round whose ``op_name`` path names no ``fed.*`` scope
but, at most, a bare ``fed.local_train`` (``reduce_booked.py``, one rule for
every cell, no scope list). The accepted reader applies to the cells that
list ``scopes`` / ``scopes_swa_moe`` / ``scopes_unread``, which this cell's
file leaves out; with this one, booked + unbooked + containers add up to
``device_ms.round`` here too.
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
    return rb.per_round(lambda r: r["unbooked_ns"])
