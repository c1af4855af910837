"""The window-128 attention core's share of its roofline: the least time its
counted work could take over the device self time under
``fed.model.attn.window.core``. The work is counted from the configuration
and the mix alone (the configuration's ``counts`` module: ``q k^T`` and
``p v`` over the VISIBLE pairs only, forward and backward, no
rematerialisation), whatever implements the scope; the least time is the
larger of operations over the chip's bf16 peak and bytes over its memory's
(``peaks.json``).
"""

import os
import sys

BENCHMARK = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCHMARK not in sys.path:
    sys.path.insert(0, BENCHMARK)
import reduce_scopes_swa_moe as rsm  # noqa: E402  (benchmark/reduce_scopes_swa_moe.py)

KERNEL, SCOPE = "attn_window", "fed.model.attn.window.core"
META = {"layer": "kernels", "unit": "%", "moves": "rounds_per_s"}


def applies(cell: dict) -> bool:
    return rsm.lists_scope(cell, SCOPE)


def read(summary: dict):
    return rsm.roofline_pct(summary, KERNEL, SCOPE)
