"""``reduce_scopes.py`` read with the scopes of a hybrid state-space model in
the adapter round: ``fed.model.ssm`` (``.conv``, ``.scan``),
``fed.model.attn`` (``.core``), ``fed.model.mlp``, ``fed.model.lora``,
``fed.model.head``. ``reduce_scopes.SCOPES`` is the Qwen3-Next cell's list
and books an operation under the innermost scope IT knows, so there a
low-rank pair's products count under the layer they stand in
(``device_ms.attn.round``); here ``fed.model.lora`` is a scope of its own and
its time is left out of the layers'.

This file loads a second copy of that module and gives it this list: the
wire reader, the window, the self-time rule and the roofline arithmetic are
that file's, unchanged. A program without these scopes gives every reader
``None``.
"""

from __future__ import annotations

import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)
import reduce_scopes as rsc  # noqa: E402  (benchmark/reduce_scopes.py)

SCOPES = ("fed.model.ssm.scan", "fed.model.ssm.conv", "fed.model.ssm",
          "fed.model.attn.core", "fed.model.attn", "fed.model.mlp",
          "fed.model.lora", "fed.model.head", "fed.client_fold")

_reducer = rsc._load(os.path.join(HERE, "reduce_scopes.py"),
                     "bench_reduce_scopes_hybrid")
_reducer.SCOPES = SCOPES
# longest first, so that ``fed.model.ssm.scan`` is not read as its parent
_reducer._SCOPE = re.compile("|".join(
    re.escape(s) for s in sorted(SCOPES, key=len, reverse=True)))

scope_ms = _reducer.scope_ms
roofline_pct = _reducer.roofline_pct
lists_scope = rsc.lists_scope
