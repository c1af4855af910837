"""``reduce_scopes.py`` read with the scopes of a window / full attention
mixture-of-experts model in the adapter round: ``fed.model.attn.window`` and
``fed.model.attn.full`` (``.core``), ``fed.model.moe`` (``.route``,
``.experts``, ``.shared``), ``fed.model.mlp`` (the dense layer's),
``fed.model.lora``, ``fed.model.head``. As in ``reduce_scopes_hybrid.py``,
``fed.model.lora`` is a scope of its own here and its time is left out of the
layer a pair stands in; the held experts' pairs carry no such scope (they are
grouped by the experts' own assignment) and are ``fed.model.moe.experts``'s.

This file loads a third copy of that module and gives it this list: the wire
reader, the window, the self-time rule and the roofline arithmetic are that
file's, unchanged. A program without these scopes gives every reader
``None``. Which scopes and counters this partition's readers read in a cell
is the configuration file's ``scopes_swa_moe`` / ``counters_swa_moe`` (its
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

SCOPES = ("fed.model.attn.window.core", "fed.model.attn.window",
          "fed.model.attn.full.core", "fed.model.attn.full",
          "fed.model.moe.route", "fed.model.moe.experts",
          "fed.model.moe.shared", "fed.model.moe", "fed.model.mlp",
          "fed.model.lora", "fed.model.head", "fed.client_fold")

_reducer = rsc._load(os.path.join(HERE, "reduce_scopes.py"),
                     "bench_reduce_scopes_swa_moe")
_reducer.SCOPES = SCOPES
# longest first, so that ``fed.model.attn.window.core`` is not read as its
# parent
_reducer._SCOPE = re.compile("|".join(
    re.escape(s) for s in sorted(SCOPES, key=len, reverse=True)))

scope_ms = _reducer.scope_ms
roofline_pct = _reducer.roofline_pct


def lists_scope(cell: dict, scope: str) -> bool:
    """Whether the cell's configuration file lists ``scope`` among those
    this partition's readers read (``scopes_swa_moe``)."""
    return scope in rsc._config_of(cell).get("scopes_swa_moe", [])


def lists_counter(cell: dict, counter: str) -> bool:
    return counter in rsc._config_of(cell).get("counters_swa_moe", [])
