"""``reduce_scopes.py`` read with the scopes of a stack of single-mixer blocks
(state-space, attention, routed experts) in the adapter round:
``fed.model.ssm`` (``.conv``, ``.scan``), ``fed.model.attn`` (``.core``),
``fed.model.moe`` (``.route``, ``.experts``, ``.shared``), ``fed.model.norm``
(a block's input norm), ``fed.model.lora``, ``fed.model.head``. As in
``reduce_scopes_swa_moe.py``, ``fed.model.lora`` is a scope of its own here
and its time is left out of the mixer a pair stands in; the held experts'
pairs carry no such scope (they are grouped by the experts' own assignment)
and are ``fed.model.moe.experts``'s.

This file loads a fourth copy of that module and gives it this list: the wire
reader, the window, the self-time rule and the roofline arithmetic are that
file's, unchanged. A program without these scopes gives every reader
``None``. Which scopes and counters this partition's readers read in a cell
is the configuration file's ``scopes_ssm_moe`` / ``counters_ssm_moe`` (its
``scopes`` / ``counters`` are what the accepted readers key on).
"""

from __future__ import annotations

import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)
import reduce_scopes as rsc  # noqa: E402  (benchmark/reduce_scopes.py)

SCOPES = ("fed.model.ssm.conv", "fed.model.ssm.scan", "fed.model.ssm",
          "fed.model.attn.core", "fed.model.attn", "fed.model.moe.route",
          "fed.model.moe.experts", "fed.model.moe.shared", "fed.model.moe",
          "fed.model.norm", "fed.model.lora", "fed.model.head",
          "fed.client_fold")

_reducer = rsc._load(os.path.join(HERE, "reduce_scopes.py"),
                     "bench_reduce_scopes_ssm_moe")
_reducer.SCOPES = SCOPES
# longest first, so that ``fed.model.ssm.scan`` is not read as its parent
_reducer._SCOPE = re.compile("|".join(
    re.escape(s) for s in sorted(SCOPES, key=len, reverse=True)))

scope_ms = _reducer.scope_ms
roofline_pct = _reducer.roofline_pct


def lists_scope(cell: dict, scope: str) -> bool:
    """Whether the cell's configuration file lists ``scope`` among those
    this partition's readers read (``scopes_ssm_moe``)."""
    return scope in rsc._config_of(cell).get("scopes_ssm_moe", [])


def lists_counter(cell: dict, counter: str) -> bool:
    return counter in rsc._config_of(cell).get("counters_ssm_moe", [])
