"""What a round's clients would have uploaded, in megabytes: the program's
``adapter_bytes_folded`` counter (``FedAdapterAPI.adapter_profile``: after
every host-loop round, the clients of its cohort whose weight was positive x
the adapter tree's bytes), read by the runner before and after the window,
over the window's rounds. A size, not a time: it falls where a silo is empty
or a cohort short, and with the adapters' rank and sites; in a cell whose
cohorts are always full it reads the same every run. The frozen base is never
part of it.
"""

import os
import sys

BENCHMARK = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCHMARK not in sys.path:
    sys.path.insert(0, BENCHMARK)
import reduce_scopes as rsc  # noqa: E402  (benchmark/reduce_scopes.py)

COUNTER = "adapter_bytes_folded"
META = {"layer": "client parallelism", "unit": "MB", "moves": "rounds_per_s"}


def applies(cell: dict) -> bool:
    return rsc.lists_counter(cell, COUNTER)


def read(summary: dict):
    return summary.get("adapter_upload_mb_round")
