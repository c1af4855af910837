"""Mamba-2's state-space scan's share of its roofline: the least time its
counted work could take over the device self time under
``fed.model.ssm.scan``. The work is counted from the configuration and the
mix alone (the configuration's ``counts`` module: operations and bytes of the
recurrence as it is stated, forward and backward, no rematerialisation),
whatever implements the scope; the least time is the larger of operations over
the chip's bf16 peak and bytes over its memory's (``peaks.json``).
"""

import os
import sys

BENCHMARK = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCHMARK not in sys.path:
    sys.path.insert(0, BENCHMARK)
import reduce_scopes_hybrid as rsh  # noqa: E402  (benchmark/reduce_scopes_hybrid.py)

KERNEL, SCOPE = "ssm_scan", "fed.model.ssm.scan"
META = {"layer": "kernels", "unit": "%", "moves": "rounds_per_s"}


def applies(cell: dict) -> bool:
    return rsh.lists_scope(cell, SCOPE)


def read(summary: dict):
    return rsh.roofline_pct(summary, KERNEL, SCOPE)
