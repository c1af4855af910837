"""Self time of the first device's operations a traced round under the
``fed.model.moe`` scopes: router, top-k and sort (``.route``), the held
experts' grouped products (``.experts``) and the shared expert (``.shared``).
"""

import os
import sys

BENCHMARK = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCHMARK not in sys.path:
    sys.path.insert(0, BENCHMARK)
import reduce_scopes as rsc  # noqa: E402  (benchmark/reduce_scopes.py)

SCOPE = "fed.model.moe"
META = {"layer": "model layers", "unit": "ms", "moves": "rounds_per_s"}


def applies(cell: dict) -> bool:
    return rsc.lists_scope(cell, SCOPE)


def read(summary: dict):
    return rsc.scope_ms(SCOPE)
