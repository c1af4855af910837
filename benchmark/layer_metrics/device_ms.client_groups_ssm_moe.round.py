"""``device_ms.client_groups.round``'s reading in a cell that trains its
cohort some clients at a time through its own ``client_group_size_ssm_moe``
(the accepted reader keys on ``fed_config.client_group_size``): self time of
the first device's operations a traced round whose innermost ``fed.*`` name
is ``fed.client_groups`` (``parallel/shard.fold_client_groups``): the group
loop's container, the empty running sum and what XLA copies around the loop;
the body's own operations keep their phase and scope (``reduce_booked.py``).
"""

import os
import sys

BENCHMARK = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCHMARK not in sys.path:
    sys.path.insert(0, BENCHMARK)
import reduce_booked as rb  # noqa: E402  (benchmark/reduce_booked.py)
import reduce_scopes as rsc  # noqa: E402  (benchmark/reduce_scopes.py)

SCOPE = "fed.client_groups"
META = {"layer": "client parallelism", "unit": "ms", "moves": "rounds_per_s"}


def applies(cell: dict) -> bool:
    return bool(rsc._config_of(cell).get("client_group_size_ssm_moe"))


def read(summary: dict):
    return rb.innermost_ms(SCOPE)
