"""Self time of the first device's operations a traced round under
``fed.model.mlp``: the shared gated MLP after every mixer (two frozen
products and the gate), forward, backward and rematerialised; its low-rank
pairs are ``device_ms.lora.round``'s.
"""

import os
import sys

BENCHMARK = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCHMARK not in sys.path:
    sys.path.insert(0, BENCHMARK)
import reduce_scopes_hybrid as rsh  # noqa: E402  (benchmark/reduce_scopes_hybrid.py)

SCOPE = "fed.model.mlp"
META = {"layer": "model layers", "unit": "ms", "moves": "rounds_per_s"}


def applies(cell: dict) -> bool:
    return rsh.lists_scope(cell, SCOPE)


def read(summary: dict):
    return rsh.scope_ms(SCOPE)
