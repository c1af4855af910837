"""The grouped Mamba-2 scan's share of its roofline: the least time the
recurrence's counted work could take over the device self time under
``fed.model.ssm.scan``. The work is counted from the configuration and the
mix alone (the configuration's ``counts`` module: the recurrence as stated,
a head a token, forward and both gradients; ``B`` and ``C`` of the groups
read once; nothing recomputed), whatever implements the scope; the least
time is the larger of operations over the chip's bf16 peak and bytes over its
memory's (``peaks.json``).
"""

import os
import sys

BENCHMARK = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCHMARK not in sys.path:
    sys.path.insert(0, BENCHMARK)
import reduce_scopes_ssm_moe as rsm  # noqa: E402  (benchmark/reduce_scopes_ssm_moe.py)

KERNEL, SCOPE = "ssm_groups_scan", "fed.model.ssm.scan"
META = {"layer": "kernels", "unit": "%", "moves": "rounds_per_s"}


def applies(cell: dict) -> bool:
    return rsm.lists_scope(cell, SCOPE)


def read(summary: dict):
    return rsm.roofline_pct(summary, KERNEL, SCOPE)
