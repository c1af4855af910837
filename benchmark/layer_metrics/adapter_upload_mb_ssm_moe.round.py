"""``adapter_upload_mb.round``'s reading in a cell that lists the program's
``adapter_bytes_folded`` under ``counters_ssm_moe``: what a round's clients
would have uploaded, in megabytes (``FedAdapterAPI.adapter_profile``: after
every host-loop round, the clients of its cohort whose weight was positive x
the adapter tree's bytes), read by the runner before and after the window,
over the window's rounds. A size, not a time: it reads the same every run of
a cell whose cohorts are always full, and moves with the adapters' rank and
sites (the held experts' pairs are most of it here).
"""

import os
import sys

BENCHMARK = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCHMARK not in sys.path:
    sys.path.insert(0, BENCHMARK)
import reduce_scopes_ssm_moe as rsm  # noqa: E402  (benchmark/reduce_scopes_ssm_moe.py)

COUNTER = "adapter_bytes_folded"
META = {"layer": "client parallelism", "unit": "MB", "moves": "rounds_per_s"}


def applies(cell: dict) -> bool:
    return rsm.lists_counter(cell, COUNTER)


def read(summary: dict):
    return summary.get("adapter_upload_mb_round")
