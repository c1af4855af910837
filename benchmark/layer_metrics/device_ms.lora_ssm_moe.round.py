"""``device_ms.lora.round``'s reading in a stack of single-mixer blocks: self
time of the first device's operations a traced round under ``fed.model.lora``:
the two products of every low-rank pair beside a dense projection (Mamba-2's
``in_proj`` / ``out_proj``, attention's four, the shared experts' two),
forward, rematerialised, and their backward; the held experts' pairs are
grouped by the experts' own assignment and are ``fed.model.moe.experts``'s
(``reduce_scopes_ssm_moe.py``).
"""

import os
import sys

BENCHMARK = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCHMARK not in sys.path:
    sys.path.insert(0, BENCHMARK)
import reduce_scopes_ssm_moe as rsm  # noqa: E402  (benchmark/reduce_scopes_ssm_moe.py)

SCOPE = "fed.model.lora"
META = {"layer": "model layers", "unit": "ms", "moves": "rounds_per_s"}


def applies(cell: dict) -> bool:
    return rsm.lists_scope(cell, SCOPE)


def read(summary: dict):
    return rsm.scope_ms(SCOPE)
