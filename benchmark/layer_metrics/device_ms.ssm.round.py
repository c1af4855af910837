"""Self time of the first device's operations a traced round under the
``fed.model.ssm`` scopes: the Mamba-2 mixers' frozen projections, the causal
convolution (``.conv``), the gated norm and the chunked state-space scan
(``.scan``), forward, backward and rematerialised; their low-rank pairs are
``device_ms.lora.round``'s.
"""

import os
import sys

BENCHMARK = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCHMARK not in sys.path:
    sys.path.insert(0, BENCHMARK)
import reduce_scopes_hybrid as rsh  # noqa: E402  (benchmark/reduce_scopes_hybrid.py)

SCOPE = "fed.model.ssm"
META = {"layer": "model layers", "unit": "ms", "moves": "rounds_per_s"}


def applies(cell: dict) -> bool:
    return rsh.lists_scope(cell, SCOPE)


def read(summary: dict):
    return rsh.scope_ms(SCOPE)
