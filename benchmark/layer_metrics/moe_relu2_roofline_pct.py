"""The held ``relu^2`` experts' product's share of its roofline: the least time
its counted work could take over the device self time under
``fed.model.moe.experts``. The work is counted from the configuration and the
mix alone (the configuration's ``counts`` module: the real assignments' two
products with the frozen matrices forward and backward with respect to the
activations, their two pairs with both gradients, every held expert's
matrices read once a pass at their published width; no padded row or column,
no masked pair column, no rematerialisation), whatever implements the scope;
the least time is the larger of operations over the chip's bf16 peak and
bytes over its memory's (``peaks.json``).
"""

import os
import sys

BENCHMARK = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCHMARK not in sys.path:
    sys.path.insert(0, BENCHMARK)
import reduce_scopes_ssm_moe as rsm  # noqa: E402  (benchmark/reduce_scopes_ssm_moe.py)

KERNEL, SCOPE = "moe_relu2", "fed.model.moe.experts"
META = {"layer": "kernels", "unit": "%", "moves": "rounds_per_s"}


def applies(cell: dict) -> bool:
    return rsm.lists_scope(cell, SCOPE)


def read(summary: dict):
    return rsm.roofline_pct(summary, KERNEL, SCOPE)
