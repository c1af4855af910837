"""Self time of the first device's operations a traced round under all of
``fed.model.attn`` in a model whose attention serves 16 query heads a
key-value head with no positions: the frozen q, k, v, o projections, the
repeat of the key-value heads and the causal core (``.core``), forward,
backward and rematerialised; the projections' pairs are under
``fed.model.lora`` and left out (``reduce_scopes_ssm_moe.py``).
"""

import os
import sys

BENCHMARK = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCHMARK not in sys.path:
    sys.path.insert(0, BENCHMARK)
import reduce_scopes_ssm_moe as rsm  # noqa: E402  (benchmark/reduce_scopes_ssm_moe.py)

SCOPE = "fed.model.attn"
META = {"layer": "model layers", "unit": "ms", "moves": "rounds_per_s"}


def applies(cell: dict) -> bool:
    return rsm.lists_scope(cell, SCOPE)


def read(summary: dict):
    return rsm.scope_ms(SCOPE)
