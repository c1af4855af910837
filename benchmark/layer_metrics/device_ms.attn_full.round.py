"""Self time of the first device's operations a traced round under the
``fed.model.attn.full`` scopes: the full (causal, no positions) attention
layers' frozen projections, the norms a head and the flash kernel
(``.core``), forward, backward and rematerialised; their low-rank pairs are
under ``fed.model.lora`` and left out (``reduce_scopes_swa_moe.py``).
"""

import os
import sys

BENCHMARK = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCHMARK not in sys.path:
    sys.path.insert(0, BENCHMARK)
import reduce_scopes_swa_moe as rsm  # noqa: E402  (benchmark/reduce_scopes_swa_moe.py)

SCOPE = "fed.model.attn.full"
META = {"layer": "model layers", "unit": "ms", "moves": "rounds_per_s"}


def applies(cell: dict) -> bool:
    return rsm.lists_scope(cell, SCOPE)


def read(summary: dict):
    return rsm.scope_ms(SCOPE)
