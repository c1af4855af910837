#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, which is the only thing that touches JAX (a chip belongs to one
process at a time), drives the three device paths once through the entry
points a user calls, at the full width of models the repo runs, with random
weights from a seed:

- ``train_resnet56`` (the main path): ``fedml_tpu.exp.run.run`` — the body of
  ``python -m fedml_tpu.exp.main_fedavg`` — on the bench primary's config
  (ResNet-56, bf16 client step, 128 clients x 256 synthetic CIFAR samples,
  cohort 8, batch 32): 3 fused donated rounds, then the on-device scan;
- ``train_transformer``: a FedAvg round of the d512 x 4 transformer LM at
  T=512, once with dense attention and once with the pallas flash kernels;
- ``kernels``: flash attention forward + all three gradients at T=2048 for
  d_head 64 and 128 and at Granite 4.0-H's 32 heads of 64 with its softmax
  scale 1/64, Mamba-2's chunked scan at Granite's 64 heads of 64 x 128 with
  all five gradients (XLA, no Mosaic call), the gated delta rule's hand-over
  kernels with all five
  gradients at Qwen3-Next's head size (128 x 128, chunk 64), the frozen
  projection with its low-rank pair in one pass (``ops/lora_linear.py``) at
  Granite's ``input_linear`` (2,048 -> 16,384, plain and gated, the base
  unbatched), and the fused
  GroupNorm at ResNet-56's shapes, under a vmap over clients, against
  float32 ``jax.numpy`` references; the ``GatedDeltaNet`` layer at the
  published heads (16 key, 32 value) is lowered and its Mosaic calls counted;
- ``adapter_round``: a FedAdapter round over the frozen d512 x 4 base, an
  operand of the round's program (``base_bytes_operand``);
- ``serve``: 64 requests through ``ServeManager`` (batched multi-adapter
  prefill + KV-cached decode), one row checked against the B=1 path;
- ``timing_facts``: does ``block_until_ready`` wait, does the profiler work;
- ``multi_device``: the main path sharded over every local device (a visible
  skip on one device).

Every phase checks finite losses, results on the expected device, no
recompile on the second call of its programs, and — where a pallas kernel is
involved — the Mosaic custom call in the lowered module. ``compile_s`` (first
call) and ``run_s`` (second call, ending in ``block_until_ready``) are set-up
facts, not rates. A failing phase records its traceback and the next phase
still runs, but any failure makes ``"ok": false`` and a non-zero exit.

Without a TPU this exits non-zero before any phase and prints no result.
``--dryrun-cpu`` is a named debugging mode, never a fallback: the same phases
at toy widths on the CPU with interpreted kernels, reported as
``"platform": "cpu", "dryrun": true``.

The last line of stdout is the verdict, one JSON object with exactly these
keys: ``{"ok": ..., "device": {"platform": ..., "kind": ..., "count": ...}}``.
The line before it is the summary (versions, cache directory, every phase's
record); the full report goes to ``chiprun_out/chip_smoke.json``.
"""

from __future__ import annotations

import argparse
import dataclasses
import faulthandler
import json
import os
import sys
import threading
import time
import traceback
from functools import partial

HERE = os.path.dirname(os.path.abspath(__file__))
_T0 = time.perf_counter()
#: Hard stop, inside the 1200 s the chip check allows: a hang must end as a
#: failed run, not as a command killed at its time limit.
DEADLINE_S = 1100.0
PROFILER_LIMIT_S = 240.0
#: Kernel outputs and gradients are bf16 computed from bf16 operands (one
#: rounding of 2^-8 per cast, a few casts in sequence, f32 accumulation):
#: max|got - ref| <= KERNEL_TOL * max(1, max|ref|) against the float32
#: reference.
KERNEL_TOL = 2e-2
#: Dense and flash transformer rounds start from the same weights and data;
#: their first-round mean losses differ only by attention rounding.
ATTN_LOSS_RTOL = 1e-2
#: The verify skill's bound for sharded vs unsharded float32 parameters
#: (the psum associates the f32 sum differently than the vmap einsum).
MESH_RTOL, MESH_ATOL = 1e-5, 1e-6


@dataclasses.dataclass(frozen=True)
class Sizes:
    resnet: str
    clients: int
    per_client: int
    cohort: int
    batch: int
    vocab: int
    d_model: int
    n_heads: int
    n_layers: int
    seq_len: int
    lm_clients: int
    lm_per_client: int
    lm_batch: int
    lm_cohort: int
    kernel_t: int
    kernel_heads: tuple      # ((H, d_head), ...)
    gdn_heads: tuple         # (key heads, value heads, d_head)
    granite_attn: tuple      # (query heads, d_head, softmax scale)
    ssd_heads: tuple         # (heads, d_head, d_state, chunk)
    lora_linear: tuple       # (rows a client, depth, columns, rank)
    window_attn: tuple       # (query heads, key-value heads, d_head, window)
    held_experts: tuple      # (tokens, d, f, experts, held, top-k, rank)
    gn_shapes: tuple         # ((height == width, channels), ...)
    gn_batch: int
    serve_seq: int
    serve_batch: int
    serve_new: int
    serve_requests: int
    chain_dim: int
    chain_s: float


REAL = Sizes(
    resnet="resnet56", clients=128, per_client=256, cohort=8, batch=32,
    vocab=10004, d_model=512, n_heads=8, n_layers=4, seq_len=512,
    lm_clients=16, lm_per_client=32, lm_batch=8, lm_cohort=8,
    kernel_t=2048, kernel_heads=((8, 64), (4, 128)), gdn_heads=(16, 32, 128),
    granite_attn=(32, 64, 0.015625), ssd_heads=(64, 64, 128, 256),
    lora_linear=(1024, 2048, 16384, 16),
    window_attn=(16, 2, 128, 128), held_experts=(2048, 1024, 512, 64, 8, 8, 16),
    gn_shapes=((32, 16), (8, 256)), gn_batch=32,
    serve_seq=128, serve_batch=32, serve_new=16, serve_requests=64,
    chain_dim=4096, chain_s=0.5)
TOY = Sizes(
    resnet="resnet20", clients=8, per_client=16, cohort=4, batch=8,
    vocab=64, d_model=32, n_heads=2, n_layers=1, seq_len=32,
    lm_clients=4, lm_per_client=4, lm_batch=2, lm_cohort=2,
    kernel_t=128, kernel_heads=((2, 16), (1, 32)), gdn_heads=(1, 2, 128),
    granite_attn=(2, 16, 0.0625), ssd_heads=(2, 16, 16, 32),
    lora_linear=(256, 128, 65536, 4),
    window_attn=(4, 2, 16, 24), held_experts=(64, 32, 16, 16, 4, 3, 4),
    gn_shapes=((8, 16), (4, 32)), gn_batch=4,
    serve_seq=16, serve_batch=4, serve_new=3, serve_requests=8,
    chain_dim=256, chain_s=0.05)


def log(msg: str) -> None:
    print(f"[chip_smoke +{time.perf_counter() - _T0:.0f}s] {msg}",
          file=sys.stderr, flush=True)


class Ctx:
    """What the phases share: sizes, the expected platform, how a hang is
    reported, and what a later phase needs from an earlier one."""

    def __init__(self, sizes: Sizes, platform: str, dryrun: bool, on_hang):
        self.sizes = sizes
        self.platform = platform
        self.dryrun = dryrun
        self.on_hang = on_hang      # prints the failed summary
        self.resnet_api = None      # train_resnet56 -> timing_facts

    def resnet_argv(self, *more):
        """The README quick-start flags at the bench primary's sizes
        (a flag given again in ``more`` wins: argparse keeps the last)."""
        s = self.sizes
        return ["--model", s.resnet, "--dataset", "cifar10",
                "--synthetic_samples", str(s.clients * s.per_client),
                "--partition_method", "homo",
                "--client_num_in_total", str(s.clients),
                "--client_num_per_round", str(s.cohort),
                "--batch_size", str(s.batch), "--lr", "0.1",
                "--epochs", "1", "--comm_round", "3", "--ci", "1", *more]


# -- checks every phase shares ------------------------------------------------

def check(cond, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def check_finite(name: str, values) -> None:
    import numpy as np

    arr = np.asarray(values, np.float64)
    check(arr.size > 0 and np.isfinite(arr).all(),
          f"{name} not finite: {arr.tolist()}")


def check_on_device(name: str, tree, platform: str) -> None:
    import jax

    leaves = jax.tree.leaves(tree)
    check(leaves, f"{name}: nothing to check")
    for leaf in leaves:
        check(isinstance(leaf, jax.Array), f"{name}: {type(leaf)} is not a "
              "device array")
        bad = [d for d in leaf.devices() if d.platform != platform]
        check(not bad, f"{name}: lives on {bad}, expected {platform}")


def timed(fn):
    """``(seconds, result)`` of ``fn()``, ended by ``block_until_ready``."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return time.perf_counter() - t0, out


def timed_steady(fn):
    """Second call of a warmed program: like :func:`timed`, and it must not
    compile anything (obs/sanitizer counts compilations)."""
    from fedml_tpu.obs.sanitizer import sanitized

    with sanitized(transfer="allow", max_compiles=0):
        return timed(fn)


def mosaic_calls(ctx: Ctx, lowered, what: str) -> int:
    """Mosaic custom calls in a lowered module. On the chip a program with
    a pallas kernel must contain them — the kernel compiled, it was not
    interpreted; the CPU dry run interprets and has none."""
    n = lowered.as_text().count("tpu_custom_call")
    check(n == 0 if ctx.dryrun else n > 0,
          f"{what}: {n} Mosaic custom calls in the lowered module "
          f"({'dry run interprets' if ctx.dryrun else 'kernel not compiled'})")
    return n


def rel_err(got, ref) -> float:
    import numpy as np

    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    check(got.shape == ref.shape, f"shape {got.shape} != {ref.shape}")
    check_finite("kernel output", got)
    return float(np.max(np.abs(got - ref)) / max(1.0, np.max(np.abs(ref))))


def token_fed(n_clients, per_client, batch, t, vocab, seed=0):
    """Synthetic next-token federation from a seed: tokens in [1, vocab) so
    ``pad_id=0`` never collides."""
    import numpy as np

    from fedml_tpu.data.batching import build_federated_arrays
    from fedml_tpu.data.partition import partition_homo

    seqs = np.random.RandomState(seed).randint(
        1, vocab, size=(n_clients * per_client, t + 1))
    x = seqs[:, :t].astype(np.int32)
    y = seqs[:, 1:].astype(np.int32)
    return build_federated_arrays(x, y, partition_homo(len(x), n_clients),
                                  batch)


def lm_model(ctx: Ctx, **kw):
    from fedml_tpu.models import create_model

    s = ctx.sizes
    return create_model("transformer_lm", vocab_size=s.vocab,
                        d_model=s.d_model, n_heads=s.n_heads,
                        n_layers=s.n_layers, max_len=s.seq_len,
                        dtype="bf16", **kw)


def lm_api(ctx: Ctx, api_cls, model):
    from fedml_tpu.algos.config import FedConfig
    from fedml_tpu.trainer.local import seq_softmax_ce

    s = ctx.sizes
    fed = token_fed(s.lm_clients, s.lm_per_client, s.lm_batch, s.seq_len,
                    s.vocab)
    cfg = FedConfig(client_num_in_total=s.lm_clients,
                    client_num_per_round=s.lm_cohort, comm_round=1,
                    epochs=1, batch_size=s.lm_batch, lr=0.1)
    return api_cls(model, fed, None, cfg,
                   loss_fn=partial(seq_softmax_ce, pad_id=0))


def two_rounds(ctx: Ctx, api, out: dict) -> None:
    """Round 0 (compiles) and round 1 (must not) of ``api``."""
    def round_(r):
        return api.train_one_round(r), api.net.params

    out["compile_s"], (m0, _) = timed(lambda: round_(0))
    out["run_s"], (m1, _) = timed_steady(lambda: round_(1))
    out["losses"] = [m0["train_loss"], m1["train_loss"]]
    check_finite("train_loss", out["losses"])
    check_on_device("net.params", api.net.params, ctx.platform)


def lower_round(api):
    """The jitted round of ``api``, lowered on the shapes of round 0's
    cohort (shapes, not arrays: the layout of the operands is then the
    round's own choice, whatever the cohort gathered here lies on)."""
    import jax
    import jax.numpy as jnp

    from fedml_tpu.data.batching import gather_clients

    idx, wmask = api.sample_round(0)
    sub = gather_clients(api.train_fed, idx)
    w = sub.counts.astype(jnp.float32) * jnp.asarray(wmask)
    x, y, mask, ws = (jax.ShapeDtypeStruct(a.shape, a.dtype)
                      for a in (sub.x, sub.y, sub.mask, w))
    return api.round_fn.lower(api.net, x, y, mask, ws, ws,
                              jax.random.PRNGKey(0)), sub, w


# -- phases -------------------------------------------------------------------

def phase_train_resnet56(ctx: Ctx, out: dict) -> None:
    import jax
    import numpy as np

    from fedml_tpu.exp.args import parse_args
    from fedml_tpu.exp.run import run

    # What `python -m fedml_tpu.exp.main_fedavg <argv>` runs (exp/run.main
    # is parse_args + run + a print), kept so later phases can use the api.
    api, history = run(parse_args(ctx.resnet_argv(
        "--client_step_dtype", "bf16")), "FedAvg")
    check(len(history) == 3, f"expected 3 rounds, got {len(history)}")
    out["round_compile_s"] = history[0]["time/round_s"]
    out["losses"] = [h["train_loss"] for h in history]
    out["final_eval"] = {k: history[-1][k] for k in ("accuracy", "loss",
                                                     "num")}
    check_finite("train_loss", out["losses"])
    check_finite("eval", list(out["final_eval"].values()))
    check_on_device("net.params", api.net.params, ctx.platform)

    # A fourth round of the fused donated dispatch, now warm.
    old = jax.tree.leaves(api.net.params)[0]
    out["round_run_s"], (m, _) = timed_steady(
        lambda: (api.train_one_round(3), api.net.params))
    check_finite("train_loss", m["train_loss"])
    check(old.is_deleted(), "the round did not donate the old model")

    # The whole-federation scan.
    def scan():
        return api.train_rounds_on_device(3), api.net.params

    out["scan_compile_s"], (losses, _) = timed(scan)
    out["scan_run_s"], (losses, _) = timed_steady(scan)
    out["scan_losses"] = np.asarray(losses).tolist()
    check_finite("scan losses", out["scan_losses"])
    check_on_device("scan losses", losses, ctx.platform)
    out["compile_s"] = out["round_compile_s"] + out["scan_compile_s"]
    out["run_s"] = out["round_run_s"] + out["scan_run_s"]
    ctx.resnet_api = api


def phase_train_transformer(ctx: Ctx, out: dict) -> None:
    from fedml_tpu.algos.fedavg import FedAvgAPI

    for attn in ("dense", "flash"):
        sub = out[attn] = {}
        api = lm_api(ctx, FedAvgAPI, lm_model(ctx, attn=attn))
        two_rounds(ctx, api, sub)
        if attn == "flash":
            sub["mosaic_calls"] = mosaic_calls(
                ctx, lower_round(api)[0], "train_transformer[flash]")
    out["compile_s"] = out["dense"]["compile_s"] + out["flash"]["compile_s"]
    out["run_s"] = out["dense"]["run_s"] + out["flash"]["run_s"]
    d, f = out["dense"]["losses"][0], out["flash"]["losses"][0]
    out["dense_flash_loss_rel"] = abs(d - f) / abs(d)
    check(out["dense_flash_loss_rel"] <= ATTN_LOSS_RTOL,
          f"round-0 loss dense {d} vs flash {f}: apart by more than "
          f"{ATTN_LOSS_RTOL}")


def phase_kernels(ctx: Ctx, out: dict) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.models.resnet import norm_groups
    from fedml_tpu.ops.flash_attention import flash_attention
    from fedml_tpu.ops.group_norm import group_norm
    from fedml_tpu.parallel.ring_attention import reference_attention

    s = ctx.sizes
    out["tolerance"] = KERNEL_TOL
    out["compile_s"] = out["run_s"] = 0.0
    n_clients = 2  # the client axis the round vmaps kernels over

    def with_grads(f):
        """``(outputs, input-gradients)`` of ``sum(f(*a) * cotangent)``."""
        def run(cot, *a):
            y, vjp = jax.vjp(f, *a)
            return y, vjp(cot.astype(y.dtype))
        return run

    def reference(f, cot, *a):
        up = [x.astype(jnp.float32) for x in a]
        with jax.default_matmul_precision("highest"):
            return jax.jit(with_grads(f))(cot.astype(jnp.float32), *up)

    def compare(name, kernel, ref_fn, cot, *a, mosaic: bool = True):
        sub = out[name] = {}
        fn = jax.jit(with_grads(kernel))
        if mosaic:      # an XLA-only op (the state-space scan) has none
            sub["mosaic_calls"] = mosaic_calls(ctx, fn.lower(cot, *a), name)
        sub["compile_s"], got = timed(lambda: fn(cot, *a))
        sub["run_s"], got = timed_steady(lambda: fn(cot, *a))
        check_on_device(name, got, ctx.platform)
        want = reference(ref_fn, cot, *a)
        errs = [rel_err(g, w) for g, w in zip(jax.tree.leaves(got),
                                              jax.tree.leaves(want))]
        sub["rel_err"] = errs
        check(max(errs) <= KERNEL_TOL,
              f"{name}: error {errs} vs float32 reference > {KERNEL_TOL}")
        out["compile_s"] += sub["compile_s"]
        out["run_s"] += sub["run_s"]

    for h, d in s.kernel_heads:
        keys = jax.random.split(jax.random.PRNGKey(h * 1000 + d), 4)
        q, k, v, do = (jax.random.normal(
            kk, (n_clients, 1, s.kernel_t, h, d), jnp.bfloat16)
            for kk in keys)
        compare(
            f"flash_h{h}_d{d}",
            jax.vmap(partial(flash_attention, causal=True)),
            jax.vmap(partial(reference_attention, causal=True)),
            do, q, k, v)       # rel_err order: o, dq, dk, dv

    # Granite 4.0-H's attention core: head 64, the model's own scale (1/64,
    # not 1/8); scaling q by scale * sqrt(d) gives the reference that scale
    h, d, scale = s.granite_attn
    keys = jax.random.split(jax.random.PRNGKey(h * 1000 + d + 1), 4)
    q, k, v, do = (jax.random.normal(
        kk, (n_clients, 1, s.kernel_t, h, d), jnp.bfloat16) for kk in keys)
    compare(
        f"flash_h{h}_d{d}_scaled",
        jax.vmap(partial(flash_attention, causal=True, scale=scale)),
        jax.vmap(lambda q, k, v: reference_attention(
            q * (scale * d ** 0.5), k, v, causal=True)),
        do, q, k, v)

    # K-EXAONE's window layers: head 128, a window of 128, a key-value head
    # a group of 8 query heads (the grouped band kernels: k and v read with
    # their own heads), against the masked plain softmax over repeated k, v
    h, hkv, d, window = s.window_attn
    keys = jax.random.split(jax.random.PRNGKey(h * 1000 + d + window), 4)
    q, do = (jax.random.normal(
        kk, (n_clients, 1, s.kernel_t, h, d), jnp.bfloat16) for kk in keys[:2])
    k, v = (jax.random.normal(
        kk, (n_clients, 1, s.kernel_t, hkv, d), jnp.bfloat16)
        for kk in keys[2:])

    def windowed(q, k, v):
        k, v = (jnp.repeat(a, h // hkv, axis=2) for a in (k, v))
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * d ** -0.5
        back = jnp.arange(q.shape[1])[:, None] - jnp.arange(q.shape[1])[None]
        scores = jnp.where((back >= 0) & (back < window), scores, -1e30)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)

    compare(
        f"flash_h{h}_kv{hkv}_d{d}_window{window}",
        jax.vmap(partial(flash_attention, causal=True, window=window)),
        jax.vmap(windowed), do, q, k, v)    # rel_err order: o, dq, dk, dv

    # the held experts' product for frozen experts with a pair a client
    # (the matrices unbatched under the vmap over clients: the grouped Mosaic
    # product over the sorted assignments, ``ops/grouped_matmul.py``), against
    # every held expert over every token masked by the routing; as the router
    # sends them, and with one held expert at 6 times the mean load
    from fedml_tpu.parallel import expert_parallel as ep

    n, d, f, experts, held, top_k, r = s.held_experts
    keys = jax.random.split(jax.random.PRNGKey(n + d + f), 12)
    x = jax.random.normal(keys[0], (n_clients, n, d), jnp.bfloat16)
    w_router = jax.random.normal(keys[1], (d, experts)) * d ** -0.5
    bias = 0.05 * jax.random.normal(keys[2], (experts,))
    w_gate_up = (jax.random.normal(keys[3], (held, d, 2 * f))
                 * d ** -0.5).astype(jnp.bfloat16)
    w_down = (jax.random.normal(keys[4], (held, f, d))
              * f ** -0.5).astype(jnp.bfloat16)
    pairs = ep.ExpertPairs(*(
        jax.random.normal(keys[5 + i], (n_clients, held) + shape) * scale
        for i, (shape, scale) in enumerate((
            ((d, r), d ** -0.5), ((r, f), r ** -0.5), ((d, r), d ** -0.5),
            ((r, f), r ** -0.5), ((f, r), f ** -0.5), ((r, d), r ** -0.5)))))
    routed, weight = jax.vmap(lambda x: ep.route_sigmoid(
        x, w_router, bias, top_k, 2.5))(x)
    rows = ep.chunk_rows(n, top_k, experts, held)
    crowd = 6 * n * top_k // experts    # tokens whose first choice is expert 0
    skewed = jnp.where(routed == 0, experts - 1, routed).at[
        :, :crowd, 0].set(0)

    for name, idx in ((f"held_lora_n{n}_d{d}_f{f}_h{held}", routed),
                      (f"held_lora_n{n}_d{d}_f{f}_h{held}_skew6", skewed)):
        def grouped(x, weight, *pairs, idx=idx):
            return jax.vmap(lambda x, i, w, p: ep.held_lora_products(
                x, w, ep.sort_held(i, held, 0), w_gate_up, w_down, p, 2.0,
                rows)[0])(x, idx, weight, ep.ExpertPairs(*pairs))

        def every_expert(x, weight, *pairs, idx=idx):
            def one(x, i, w, p):
                total = 0.0
                for e in range(held):
                    w_e = jnp.sum(jnp.where(i == e, w, 0.0), -1)
                    wgu = w_gate_up[e].astype(x.dtype)
                    gate = x @ wgu[:, :f] + 2.0 * (
                        x @ p.gate_a[e]) @ p.gate_b[e]
                    up = x @ wgu[:, f:] + 2.0 * (x @ p.up_a[e]) @ p.up_b[e]
                    hidden = jax.nn.silu(gate) * up
                    total = total + w_e[:, None] * (
                        hidden @ w_down[e].astype(x.dtype)
                        + 2.0 * (hidden @ p.down_a[e]) @ p.down_b[e])
                return total
            return jax.vmap(one)(x, idx, weight, ep.ExpertPairs(*pairs))

        compare(
            name, grouped, every_expert,
            jax.random.normal(keys[11], (n_clients, n, d), jnp.bfloat16),
            x, weight, *pairs)
        # rel_err order: y, dx, dweight, the six pairs' gradients

    # Mamba-2's chunked scan at Granite's heads against the recurrence
    from fedml_tpu.ops.ssd import ssd_recurrence, ssd_scan

    h, p, n, chunk = s.ssd_heads
    keys = jax.random.split(jax.random.PRNGKey(h + p + n), 6)
    # 1,024 tokens (Granite's): the recurrence's backward keeps a state a token
    lead = (n_clients, 1, min(s.kernel_t, 1024))
    compare(
        f"ssd_h{h}_p{p}_n{n}",
        jax.vmap(partial(ssd_scan, chunk=chunk)), jax.vmap(ssd_recurrence),
        jax.random.normal(keys[0], lead + (h, p), jnp.bfloat16),
        jax.random.normal(keys[1], lead + (h, p), jnp.bfloat16),
        jax.nn.softplus(jax.random.normal(keys[2], lead + (h,)) - 3.0),
        -jnp.exp(jax.random.uniform(keys[3], (n_clients, h), maxval=2.7)),
        (jax.random.normal(keys[4], lead + (1, n)) * n ** -0.5).astype(
            jnp.bfloat16),
        jax.random.normal(keys[5], lead + (1, n), jnp.bfloat16),
        mosaic=False)   # rel_err order: y, dx, ddt, da, db, dc

    # a frozen projection with its low-rank pair in one pass, at Granite
    # 4.0-H's input_linear (the base unbatched under the vmap over clients),
    # plain and gated, against the three products in float32
    from fedml_tpu.ops.lora_linear import lora_linear, takes_kernel

    m, k, n, r = s.lora_linear
    check(takes_kernel(m, k, n, r, True), f"{s.lora_linear} keeps XLA's path")
    keys = jax.random.split(jax.random.PRNGKey(m + k + n), 5)
    x = jax.random.normal(keys[0], (n_clients, 1, m, k), jnp.bfloat16)
    w = (jax.random.normal(keys[1], (k, n)) * k ** -0.5).astype(jnp.bfloat16)
    a = jax.random.normal(keys[2], (n_clients, k, r)) * k ** -0.5
    b = jax.random.normal(keys[3], (n_clients, r, n)) * r ** -0.5

    def plain(gate, x, w, a, b):
        y = x @ w + 2.0 * ((x @ a) @ b)
        return jax.nn.silu(y[..., :n // 2]) * y[..., n // 2:] if gate else y

    for gate in (False, True):
        compare(
            f"lora_linear{'_gate' if gate else ''}_m{m}_k{k}_n{n}",
            jax.vmap(partial(lora_linear, scale=2.0, gate=gate,
                             out_dtype=jnp.bfloat16 if gate else jnp.float32),
                     in_axes=(0, None, 0, 0)),
            jax.vmap(partial(plain, gate), in_axes=(0, None, 0, 0)),
            jax.random.normal(keys[4], (n_clients, 1, m, n // 2 if gate else n),
                              jnp.bfloat16),
            x, w, a, b)         # rel_err order: y, dx, dw, da, db

    from fedml_tpu.models.qwen3_next import GatedDeltaNet, Qwen3NextShapes
    from fedml_tpu.ops.gated_delta import (gated_delta_rule,
                                           gated_delta_rule_recurrent)

    hk, hv, d = s.gdn_heads
    keys = jax.random.split(jax.random.PRNGKey(d), 6)
    shape = (n_clients, 1, s.kernel_t, hv // hk, d)

    def unit(key):      # what the layer feeds the rule: rows of norm 1
        x = jax.random.normal(key, shape)
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True))

    compare(
        f"gated_delta_h{hv // hk}_d{d}",
        jax.vmap(partial(gated_delta_rule, chunk=64)),
        jax.vmap(gated_delta_rule_recurrent),
        jax.random.normal(keys[0], shape, jnp.bfloat16),
        (unit(keys[1]) * d ** -0.5).astype(jnp.bfloat16),
        unit(keys[2]).astype(jnp.bfloat16),
        jax.random.normal(keys[3], shape, jnp.bfloat16),
        -0.1 * jnp.exp(jax.random.normal(keys[4], shape[:-1])),
        jax.nn.sigmoid(jax.random.normal(keys[5], shape[:-1])))
    # rel_err order: o, dq, dk, dv, dg, dbeta
    layer = GatedDeltaNet(Qwen3NextShapes(
        hidden_size=s.d_model, linear_num_key_heads=hk,
        linear_num_value_heads=hv, linear_key_head_dim=d,
        linear_value_head_dim=d), jnp.bfloat16)
    x = jax.ShapeDtypeStruct((1, s.kernel_t, s.d_model), jnp.bfloat16)
    params = jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)
    # lowered, not run: no error to report (``max_rel_err`` reads every entry)
    out["gated_deltanet_layer"] = {"rel_err": [], "mosaic_calls": mosaic_calls(
        ctx, jax.jit(jax.grad(lambda p, a: jnp.sum(layer.apply(p, a)))).lower(
            params, x), "GatedDeltaNet forward + backward")}

    def gn_reference(groups, x, gamma, beta):
        n, hh, ww, c = x.shape
        xg = x.reshape(n, hh * ww, groups, c // groups)
        mu = xg.mean(axis=(1, 3), keepdims=True)
        var = jnp.square(xg - mu).mean(axis=(1, 3), keepdims=True)
        xhat = ((xg - mu) * jax.lax.rsqrt(var + 1e-6)).reshape(x.shape)
        return xhat * gamma + beta

    for hw, c in s.gn_shapes:
        g = norm_groups(c)
        keys = jax.random.split(jax.random.PRNGKey(hw * 1000 + c), 4)
        shape = (n_clients, s.gn_batch, hw, hw, c)
        x = jax.random.normal(keys[0], shape, jnp.bfloat16)
        dy = jax.random.normal(keys[1], shape, jnp.bfloat16)
        gamma = 1.0 + 0.1 * jax.random.normal(keys[2], (n_clients, c))
        beta = 0.1 * jax.random.normal(keys[3], (n_clients, c))
        compare(
            f"group_norm_hw{hw}_c{c}",
            jax.vmap(lambda a, ga, be: group_norm(a, ga, be, g)),
            jax.vmap(partial(gn_reference, g)),
            dy, x, gamma, beta)  # rel_err order: y, dx, dgamma, dbeta
    out["max_rel_err"] = float(np.max(
        [e for v in out.values() if isinstance(v, dict)
         for e in v["rel_err"]]))


def phase_adapter_round(ctx: Ctx, out: dict) -> None:
    import jax
    import numpy as np

    from fedml_tpu.algos.fedadapter import FedAdapterAPI

    api = lm_api(ctx, FedAdapterAPI,
                 lm_model(ctx, adapter_rank=8, adapter_scope="all"))
    out.update(api.adapter_profile())
    check_on_device("frozen base", api.base, ctx.platform)
    base0 = jax.tree.map(np.asarray, api.base)
    adapters0 = jax.tree.map(np.asarray, api.net.params)
    two_rounds(ctx, api, out)
    same = jax.tree.map(np.array_equal, base0,
                        jax.tree.map(np.asarray, api.base))
    check(all(jax.tree.leaves(same)), "the frozen base changed")
    moved = jax.tree.map(lambda a, b: not np.array_equal(a, b), adapters0,
                         jax.tree.map(np.asarray, api.net.params))
    check(any(jax.tree.leaves(moved)), "no adapter leaf changed")


def phase_serve(ctx: Ctx, out: dict) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.models.adapter import (PersonalAdapterStore,
                                          adapter_model_fns)
    from fedml_tpu.serve import AdapterDecoder, ServeForward, ServeManager

    s = ctx.sizes
    rng = np.random.default_rng(0)
    model = lm_model(ctx, adapter_rank=8, adapter_scope="all")
    fns = adapter_model_fns(model)
    net = fns.init(jax.random.PRNGKey(0), jnp.zeros((1, s.serve_seq),
                                                   jnp.int32))
    # LoRA B starts at zero (every adapter the identity): randomize the
    # global adapters so a personalized row differs from the dense model.
    leaves, treedef = jax.tree.flatten(net.params)
    keys = jax.random.split(jax.random.PRNGKey(7), len(leaves))
    glob = jax.tree.unflatten(treedef, [
        0.05 * jax.random.normal(kk, l.shape, l.dtype)
        for kk, l in zip(keys, leaves)])
    fwd = ServeForward(fns, glob)
    dec = AdapterDecoder(model, fns, glob)
    n = s.serve_requests
    store = PersonalAdapterStore(n, glob)
    personalized = np.arange(0, n, 2)   # the odd ids read the global
    store.scatter(personalized, store.vec_of(glob)[None] + rng.normal(
        0, 0.03, (len(personalized), store.dim)).astype(np.float32))
    prompts = [rng.integers(1, s.vocab, rng.integers(s.serve_seq // 4,
                                                     s.serve_seq + 1))
               for _ in range(n)]

    def wave(mgr, ids):
        reqs = [mgr.submit(int(i), prompts[i], s.serve_new) for i in ids]
        return [r.result(timeout=DEADLINE_S) for r in reqs]

    with ServeManager(fwd, store, glob, seq_len=s.serve_seq,
                      max_batch=s.serve_batch, decoder=dec,
                      queue_cap=2 * n) as mgr:
        half = n // 2
        out["compile_s"], first = timed(lambda: wave(mgr, range(half)))
        out["run_s"], second = timed_steady(
            lambda: wave(mgr, range(half, n)))
        stats = mgr.stats()
    results = first + second
    out["stats"] = {k: stats.get(k, 0) for k in (
        "serve/admitted", "serve/served", "serve/shed", "serve/refused")}
    check(out["stats"] == {"serve/admitted": n, "serve/served": n,
                           "serve/shed": 0, "serve/refused": 0},
          f"serve counters {out['stats']} for {n} requests")
    for i, (logits, gen) in enumerate(results):
        check(logits.shape == (len(prompts[i]), s.vocab),
              f"request {i}: logits {logits.shape}")
        check_finite(f"request {i} logits", logits)
        check(gen.shape == (s.serve_new,) and (gen >= 0).all()
              and (gen < s.vocab).all(), f"request {i}: generated {gen}")

    # One personalized row against the one-request-at-a-time path.
    cid = int(personalized[1])
    vec = store.gather([cid], glob)
    toks = np.asarray(prompts[cid], np.int32)[None]
    seq_logits = fwd.prefill_sequential(vec, toks)
    one = dec.generate(fwd.stacked_tree(vec), toks, s.serve_new)
    check_on_device("B=1 generate", one, ctx.platform)
    out["b1_logits_max_abs_diff"] = float(np.max(np.abs(
        np.asarray(seq_logits[0]) - results[cid][0])))
    out["b1_tokens"] = np.asarray(one[0]).tolist()
    check(out["b1_tokens"] == results[cid][1].tolist(),
          f"client {cid}: served tokens {results[cid][1].tolist()} != the "
          f"B=1 path's {out['b1_tokens']}")


def phase_timing_facts(ctx: Ctx, out: dict) -> None:
    import glob

    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.obs.timing import trace

    # (a) Does block_until_ready wait? One dispatch of >= chain_s seconds
    # of chained matmuls, timed three ways: until the call returns (the
    # enqueue), until block_until_ready, until a host scalar fetch.
    s = ctx.sizes
    w = (jax.random.normal(jax.random.PRNGKey(0), (s.chain_dim, s.chain_dim))
         / np.sqrt(s.chain_dim)).astype(jnp.bfloat16)

    @jax.jit
    def chain(x, w, n):
        return jax.lax.fori_loop(0, n, lambda _, a: jnp.tanh(a @ w), x)

    x = jnp.ones((s.chain_dim, s.chain_dim), jnp.bfloat16)

    def fetch_s(n):
        t0 = time.perf_counter()
        float(np.asarray(chain(x, w, n)[0, 0]))
        return time.perf_counter() - t0

    fetch_s(1)  # compile
    n = 64
    while (dt := fetch_s(n)) < s.chain_s:
        n = int(n * max(2.0, 1.3 * s.chain_s / dt))
    enqueue, blocked, fetched = [], [], []
    for _ in range(3):
        t0 = time.perf_counter()
        y = chain(x, w, n)
        enqueue.append(time.perf_counter() - t0)
        jax.block_until_ready(y)
        blocked.append(time.perf_counter() - t0)
        fetched.append(fetch_s(n))
    out["chain_iters"] = n
    out["enqueue_s"] = min(enqueue)
    out["block_until_ready_s"] = min(blocked)
    out["host_fetch_s"] = min(fetched)
    out["block_until_ready_waits"] = bool(
        abs(out["block_until_ready_s"] - out["host_fetch_s"])
        <= 0.05 * out["host_fetch_s"])
    out["dispatch_is_async"] = bool(
        out["enqueue_s"] < 0.5 * out["host_fetch_s"])

    # (b) Does the profiler work? Three warm rounds of the main path.
    api = ctx.resnet_api
    check(api is not None, "needs the api of phase train_resnet56")
    trace_dir = os.path.join(HERE, "runs", "chip_smoke_trace")
    with Watchdog(PROFILER_LIMIT_S, "the profiler (timing_facts)",
                  ctx.on_hang):
        with trace(trace_dir):
            for r in range(4, 7):
                api.train_one_round(r)
            jax.block_until_ready(api.net.params)
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    check(found, f"no .xplane.pb under {trace_dir}")
    newest = max(found, key=os.path.getmtime)
    out["xplane_bytes"] = os.path.getsize(newest)
    planes = {}
    for plane in jax.profiler.ProfileData.from_file(newest).planes:
        lines = {ln.name: sum(1 for _ in ln.events) for ln in plane.lines}
        planes[plane.name] = {"events": sum(lines.values()),
                              "lines": dict(sorted(
                                  lines.items(), key=lambda kv: -kv[1])[:8])}
    out["planes"] = planes
    # The chip's plane is "/device:TPU:0"; the CPU dry run only has the
    # host's, and accepts it.
    want = "/host:" if ctx.dryrun else "/device:TPU"
    out["profiler_works"] = any(
        name.startswith(want) and p["events"] for name, p in planes.items())
    check(out["profiler_works"],
          f"no {want}* plane with events in {sorted(planes)}")


def phase_multi_device(ctx: Ctx, out: dict) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.exp.args import parse_args
    from fedml_tpu.exp.run import run

    n = len(jax.devices())
    if n == 1:
        out["skipped"] = "1 device"
        return
    out["devices"] = n
    flag = ("--num_devices", str(n))

    # Phase 1's rounds, clients sharded over every device.
    api, history = run(parse_args(ctx.resnet_argv(
        "--client_step_dtype", "bf16", *flag)), "FedAvg")
    out["compile_s"] = history[0]["time/round_s"]
    out["losses"] = [h["train_loss"] for h in history]
    check_finite("train_loss", out["losses"])
    old = jax.tree.leaves(api.net.params)[0]
    out["run_s"], (m, _) = timed_steady(
        lambda: (api.train_one_round(3), api.net.params))
    check_finite("train_loss", m["train_loss"])
    check(old.is_deleted(), "the sharded round did not donate the old model")
    check_on_device("net.params", api.net.params, ctx.platform)
    for leaf in jax.tree.leaves(api.net.params):
        held = {sh.device for sh in leaf.addressable_shards}
        check(len(held) == n, f"a parameter lives on {len(held)} of {n} "
              "devices after a sharded round")

    # The federation lies on every device, and the step that ran above
    # takes its cohort there: no collective but the aggregation's.
    for leaf in jax.tree.leaves(api.train_fed):
        check(len(leaf.sharding.device_set) == n and leaf.is_fully_replicated,
              f"the resident federation is not replicated over {n} devices")
    idx, wmask = api.sample_round(0)
    step = api._fused_round_step()[1].lower(
        api.net, api._window_carry_init(), api.train_fed, jnp.asarray(idx),
        jnp.asarray(wmask), jax.random.PRNGKey(0)).compile().as_text()
    check("all-reduce" in step, "no all-reduce in the compiled gather step")
    moved = [op for op in ("all-gather", "all-to-all", "collective-permute")
             if op in step]
    check(not moved, f"the compiled gather step moves data between devices: "
          f"{moved}")
    del step

    # The compiled round on a pre-gathered cohort (the path of a streamed or
    # eagerly gathered one): an all-reduce inside, and client-stacked
    # operands laid out as it wants them land one shard on each device.
    lowered, sub, w = lower_round(api)
    compiled = lowered.compile()
    check("all-reduce" in compiled.as_text(),
          "no all-reduce in the compiled sharded round")
    shardings = compiled.input_shardings[0]
    for name, arr, sharding in (("x", sub.x, shardings[1]),
                                ("y", sub.y, shardings[2]),
                                ("mask", sub.mask, shardings[3]),
                                ("weights", w, shardings[4])):
        placed = jax.device_put(arr, sharding)
        shards = placed.addressable_shards
        check(len({sh.device for sh in shards}) == n
              and all(sh.data.shape[0] * n == arr.shape[0] for sh in shards),
              f"{name}: {[(str(sh.device), sh.data.shape) for sh in shards]} "
              f"is not one client shard on each of {n} devices")
    out["shards_on_distinct_devices"] = n
    del api, compiled, lowered

    # Sharded against unsharded, to the verify skill's bound. The bound is
    # for what sharding changes — the psum associates the f32 sum
    # differently than the vmap einsum — so it is taken on the same data
    # and rounds with the convex model (--model lr), which keeps an ulp
    # an ulp. A fresh ResNet-56 does not: one ulp on its parameters
    # becomes 3 % of the update after ONE local step and all of it after
    # a round (measured on the CPU, PERF.md), so no bound separates a
    # sharding error from rounding there.
    params = {}
    for name, more in (("unsharded", ()), ("sharded", flag)):
        lr_api, lr_history = run(parse_args(ctx.resnet_argv(
            "--model", "lr", *more)), "FedAvg")
        check_finite("lr train_loss", [h["train_loss"] for h in lr_history])
        params[name] = jax.tree.map(np.asarray, lr_api.net.params)
    # |a - b| <= atol + rtol * |b|, as numpy.allclose; reported as the
    # largest |a - b| / (atol + rtol * |b|), which must stay <= 1.
    ratio = jax.tree.map(
        lambda a, b: float(np.max(np.abs(a - b)
                                  / (MESH_ATOL + MESH_RTOL * np.abs(b)))),
        params["sharded"], params["unsharded"])
    out["mesh_vs_unsharded_worst_ratio"] = max(jax.tree.leaves(ratio))
    check(out["mesh_vs_unsharded_worst_ratio"] <= 1.0,
          f"sharded parameters differ from mesh=None beyond rtol "
          f"{MESH_RTOL} / atol {MESH_ATOL}: worst ratio "
          f"{out['mesh_vs_unsharded_worst_ratio']}")


PHASES = {
    "train_resnet56": phase_train_resnet56,
    "train_transformer": phase_train_transformer,
    "kernels": phase_kernels,
    "adapter_round": phase_adapter_round,
    "serve": phase_serve,
    "timing_facts": phase_timing_facts,
    "multi_device": phase_multi_device,
}


# -- driver -------------------------------------------------------------------

class Watchdog:
    """Ends the process, non-zero, when its body outlives ``seconds``;
    ``on_expire(reason)`` gets to print the failed summary first."""

    def __init__(self, seconds: float, what: str, on_expire):
        self._on_expire = on_expire
        self._timer = threading.Timer(seconds, self._fire, (what, seconds))
        self._timer.daemon = True

    def __enter__(self):
        self._timer.start()
        return self

    def __exit__(self, *exc):
        self._timer.cancel()

    def _fire(self, what, seconds):
        log(f"WATCHDOG: {what} still running after {seconds:.0f}s")
        faulthandler.dump_traceback(file=sys.stderr)
        self._on_expire(f"{what} hung for {seconds:.0f}s")
        os._exit(3)


class CacheWatch:
    """Where first calls spend their time, from JAX's own monitoring:
    persistent-cache hits and misses, seconds tracing, lowering, and in
    the backend (compiling, or on a hit reading and loading the entry) —
    and the entries a phase added to the cache directory."""

    #: duration event suffix -> the name it is summed under
    SECONDS = {"/jaxpr_trace_duration": "trace_s",
               "/jaxpr_to_mlir_module_duration": "lower_s",
               "/backend_compile_duration": "backend_s",
               "/cache_retrieval_time_sec": "cache_load_s"}

    def __init__(self, cache_dir: str):
        import jax

        self.dir = cache_dir
        self.counts = dict.fromkeys(("hits", "misses"), 0)
        self.counts.update(dict.fromkeys(self.SECONDS.values(), 0.0))
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event: str, **kw) -> None:
        if event.endswith("/compilation_cache/cache_hits"):
            self.counts["hits"] += 1
        elif event.endswith("/compilation_cache/cache_misses"):
            self.counts["misses"] += 1

    def _on_duration(self, event: str, seconds: float, **kw) -> None:
        for suffix, name in self.SECONDS.items():
            if event.endswith(suffix):
                self.counts[name] += seconds

    def entries(self) -> dict:
        try:
            names = os.listdir(self.dir)
        except FileNotFoundError:
            return {}
        return {n: os.path.getsize(os.path.join(self.dir, n))
                for n in names if not n.endswith("-atime")}


def run_phases(phases: dict, ctx: Ctx, cache: CacheWatch, report: dict):
    """Run each phase; a failure is recorded and the next phase still runs.
    Returns True only if every phase passed."""
    from fedml_tpu.obs.sanitizer import compile_count

    for name, fn in phases.items():
        log(f"phase {name} ...")
        out = report["phases"][name] = {"ok": False}
        before, counts = cache.entries(), dict(cache.counts)
        compiles, t0 = compile_count(), time.perf_counter()
        try:
            fn(ctx, out)
            out["ok"] = True
        except Exception:  # noqa: BLE001 — recorded; fails the run below
            out["error"] = traceback.format_exc()
            log(f"phase {name} FAILED\n{out['error']}")
        out["wall_s"] = time.perf_counter() - t0
        out["compiles"] = compile_count() - compiles
        new = {k: v for k, v in cache.entries().items() if k not in before}
        out["cache"] = {**{k: v - counts[k] for k, v in cache.counts.items()},
                        "new_entries": len(new),
                        "new_bytes": sum(new.values()),
                        "largest_entry_bytes": max(new.values(), default=0)}
        log(f"phase {name}: {'ok' if out['ok'] else 'FAILED'} in "
            f"{out['wall_s']:.1f}s")
        print(json.dumps({name: _brief(out)}), flush=True)
    return all(p["ok"] for p in report["phases"].values())


def _round(v):
    if isinstance(v, float):
        return float(f"{v:.4g}")
    if isinstance(v, dict):
        return {k: _round(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_round(x) for x in v]
    return v


def _brief(out: dict) -> dict:
    """A phase's record for the one-line summaries: the traceback cut to
    its last line (the whole of it goes to stderr and the report file)."""
    brief = _round(out)
    if "error" in brief:
        # The exception line; JAX appends a note about filtered frames.
        lines = [ln for ln in brief["error"].splitlines()
                 if ln.strip() and not ln.startswith(("For simplicity", "-"))]
        brief["error"] = lines[-1][:300]
    return brief


def summary(report: dict) -> str:
    return json.dumps({**{k: v for k, v in report.items() if k != "phases"},
                       "phases": {n: _brief(p)
                                  for n, p in report["phases"].items()}})


def verdict(report: dict) -> str:
    """The last line of stdout: these keys and no others (the chip check
    reads it; everything else is in the summary line above it)."""
    return json.dumps({"ok": bool(report["ok"]), "device": report["device"]})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--dryrun-cpu", action="store_true",
                    help="debug the phases on the CPU at toy widths with "
                         "interpreted kernels (never a chip result)")
    ap.add_argument("--phases", default=",".join(PHASES),
                    help="comma-separated subset, for debugging one path; "
                         "the summary then says \"partial\": true")
    args = ap.parse_args(argv)
    selected = [p for p in args.phases.split(",") if p]
    unknown = sorted(set(selected) - set(PHASES))
    if unknown:
        ap.error(f"unknown phases {unknown}; known: {list(PHASES)}")

    if args.dryrun_cpu:
        # The named CPU mode places itself: two virtual devices, so that
        # the multi_device phase is debugged too.
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=2").strip()
    import jax

    # First act: a TPU, or nothing. JAX's own "TPU failed, using the CPU"
    # must not be survivable here, and neither is JAX_PLATFORMS=cpu.
    expected = "cpu" if args.dryrun_cpu else "tpu"
    backend = jax.default_backend()
    if backend != expected:
        print(f"chip_smoke: needs backend {expected!r}, JAX found "
              f"{backend!r} ({jax.devices()}); nothing was run",
              file=sys.stderr)
        return 2

    import jaxlib

    from fedml_tpu.utils import use_compile_cache

    cache_dir = use_compile_cache()
    device = jax.devices()[0]
    report = {
        "ok": False,
        "device": {"platform": device.platform, "kind": device.device_kind,
                   "count": len(jax.devices())},
        "versions": {"python": sys.version.split()[0],
                     "jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": _libtpu_version()},
        "compile_cache": cache_dir,
        "phases": {},
    }
    if args.dryrun_cpu:
        report["dryrun"] = True
    if selected != list(PHASES):
        report["partial"] = True
    print(json.dumps({k: report[k] for k in
                      ("device", "versions", "compile_cache")}), flush=True)

    out_dir = os.path.join(HERE, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)

    def finish(hung: str | None = None) -> None:
        if hung:
            report["ok"], report["hung"] = False, hung
        with open(os.path.join(out_dir, "chip_smoke.json"), "w") as f:
            json.dump(report, f, indent=1)
        sys.stderr.flush()
        print(summary(report), flush=True)
        print(verdict(report), flush=True)

    ctx = Ctx(TOY if args.dryrun_cpu else REAL, expected, args.dryrun_cpu,
              on_hang=finish)
    table = {name: PHASES[name] for name in selected}
    with Watchdog(DEADLINE_S, "chip_smoke.py", finish):
        report["ok"] = run_phases(table, ctx, CacheWatch(cache_dir), report)
    report["total_s"] = time.perf_counter() - _T0
    finish()
    return 0 if report["ok"] else 1


def _libtpu_version():
    from importlib import metadata

    try:
        return metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        return None


if __name__ == "__main__":
    sys.exit(main())
