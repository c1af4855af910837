"""Self time of the first device's operations a traced round under all of
``fed.model.moe`` in a model of non-gated ``relu^2`` experts: the 128-wide
sigmoid router and the sort of its assignments (``.route``), the held
experts' grouped product with a pair a client and an expert (``.experts``),
the shared expert's frozen products (``.shared``), forward, backward and
rematerialised; the shared expert's pairs are under ``fed.model.lora`` and
left out (``reduce_scopes_ssm_moe.py``).
"""

import os
import sys

BENCHMARK = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCHMARK not in sys.path:
    sys.path.insert(0, BENCHMARK)
import reduce_scopes_ssm_moe as rsm  # noqa: E402  (benchmark/reduce_scopes_ssm_moe.py)

SCOPE = "fed.model.moe"
META = {"layer": "model layers", "unit": "ms", "moves": "rounds_per_s"}


def applies(cell: dict) -> bool:
    return rsm.lists_scope(cell, SCOPE)


def read(summary: dict):
    return rsm.scope_ms(SCOPE)
