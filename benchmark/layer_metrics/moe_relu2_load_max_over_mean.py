"""The fullest held expert's tokens over the mean held expert's, the worst
expert block, over the window's rounds: the model's ``expert_tokens``
counter, a running total that the rounds' own program keeps in the model's
``counters`` collection and the ADAPTER round carries, read by the runner
before and after the window. 1 is a flat router; the grouped product computes
an expert's real rows only, so a full expert costs its rows and a sparser
tile, not a slab.
"""

import os
import sys

BENCHMARK = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCHMARK not in sys.path:
    sys.path.insert(0, BENCHMARK)
import reduce_scopes_ssm_moe as rsm  # noqa: E402  (benchmark/reduce_scopes_ssm_moe.py)

COUNTER = "expert_tokens"
META = {"layer": "model layers", "unit": "ratio", "moves": "rounds_per_s"}


def applies(cell: dict) -> bool:
    return rsm.lists_counter(cell, COUNTER)


def read(summary: dict):
    return summary.get("moe_relu2_load_max_over_mean")
