"""The fullest held expert's tokens over the mean held expert's, the worst
layer, over the window's rounds: the model's ``expert_tokens`` counter, a
running total that the rounds' own program keeps in the model's ``counters``
collection, read by the runner before and after the window. 1 is a flat
router; the grouped product lays the held assignments out in tiles, so what
sends the layer to its dense arm is their total, not this ratio.
"""

import os
import sys

BENCHMARK = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCHMARK not in sys.path:
    sys.path.insert(0, BENCHMARK)
import reduce_scopes as rsc  # noqa: E402  (benchmark/reduce_scopes.py)

COUNTER = "expert_tokens"
META = {"layer": "model layers", "unit": "ratio", "moves": "rounds_per_s"}


def applies(cell: dict) -> bool:
    return rsc.lists_counter(cell, COUNTER)


def read(summary: dict):
    return summary.get("moe_load_max_over_mean")
