"""Self time of the first device's operations a traced round under all of
``fed.model.ssm`` in a model whose Mamba-2 mixers hold ``B`` and ``C`` in
groups of heads: the mixers' frozen projections, the causal convolution
(``.conv``), the chunked scan (``.scan``), the skip and the gated norm a
group, forward, backward and rematerialised; the pairs of ``in_proj`` and
``out_proj`` are under ``fed.model.lora`` and left out
(``reduce_scopes_ssm_moe.py``).
"""

import os
import sys

BENCHMARK = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCHMARK not in sys.path:
    sys.path.insert(0, BENCHMARK)
import reduce_scopes_ssm_moe as rsm  # noqa: E402  (benchmark/reduce_scopes_ssm_moe.py)

SCOPE = "fed.model.ssm"
META = {"layer": "model layers", "unit": "ms", "moves": "rounds_per_s"}


def applies(cell: dict) -> bool:
    return rsm.lists_scope(cell, SCOPE)


def read(summary: dict):
    return rsm.scope_ms(SCOPE)
