"""The grouped product's fill over the window's rounds: the held experts' real
assignments (``expert_tokens``) over the rows of the chunks the product took
(``grouped_rows``: ``chunk_rows`` a chunk, the first always, further ones as
the total needs), all expert blocks; both are running totals that the rounds'
own program keeps in the model's ``counters`` collection. A chunk's rows cost
the gather, the elementwise passes, the masked pair columns and the combine
whether filled or not; the frozen products run over the real rows only.
"""

import os
import sys

BENCHMARK = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCHMARK not in sys.path:
    sys.path.insert(0, BENCHMARK)
import reduce_scopes_ssm_moe as rsm  # noqa: E402  (benchmark/reduce_scopes_ssm_moe.py)

COUNTER = "grouped_rows"
META = {"layer": "model layers", "unit": "%", "moves": "rounds_per_s"}


def applies(cell: dict) -> bool:
    return rsm.lists_counter(cell, COUNTER)


def read(summary: dict):
    return summary.get("moe_relu2_fill_pct")
