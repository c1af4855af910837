"""The causal softmax attention's share of its roofline: the least time its
counted work could take over the device self time under
``fed.model.attn.core``. The work is counted from the configuration and the mix alone
(``counts/qwen3_next.py``: operations and bytes, forward and backward, no
rematerialisation), whatever implements the scope; the least time is the
larger of operations over the chip's bf16 peak and bytes over its memory's
(``peaks.json``).
"""

import os
import sys

BENCHMARK = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCHMARK not in sys.path:
    sys.path.insert(0, BENCHMARK)
import reduce_scopes as rsc  # noqa: E402  (benchmark/reduce_scopes.py)

KERNEL, SCOPE = "attn_core", "fed.model.attn.core"
META = {"layer": "kernels", "unit": "%", "moves": "rounds_per_s"}


def applies(cell: dict) -> bool:
    return rsc.lists_scope(cell, SCOPE)


def read(summary: dict):
    return rsc.roofline_pct(summary, KERNEL, SCOPE)
