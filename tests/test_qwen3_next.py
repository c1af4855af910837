"""Qwen3-Next (``models/qwen3_next.py``) against its plain reference
(``benchmark/reference_qwen3_next.py``, the one the cell is judged by, which
imports nothing from the program: tests/test_benchmark_selfcheck.py),
on seeded weights at a small size: hidden 64, one period of 4 layers, 16
experts top-4, 2 + 4 linear heads and 4 over 2 attention heads of size 16,
vocabulary 256, T 128, chunks of 16. CPU, float32 at the highest precision
unless a test says bf16.
"""

import functools
import importlib.util
import json
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.algos.config import FedConfig
from fedml_tpu.algos.fedavg import FedAvgAPI
from fedml_tpu.data.batching import build_federated_arrays
from fedml_tpu.models import create_model
from fedml_tpu.models import qwen3_next as qn
from fedml_tpu.ops import gated_delta
from fedml_tpu.ops.gated_delta import (gated_delta_rule,
                                       gated_delta_rule_recurrent,
                                       takes_kernel)
from fedml_tpu.trainer.local import seq_softmax_ce

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_benchmark(relative: str, name: str):
    path = os.path.join(ROOT, "benchmark", relative)
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load_benchmark("reference_qwen3_next.py", "bench_reference_qwen3_next")
T, VOCAB = 128, 256
SMALL = dict(
    vocab_size=VOCAB, hidden_size=64, num_hidden_layers=4,
    full_attention_interval=4, linear_num_key_heads=2,
    linear_num_value_heads=4, linear_key_head_dim=16,
    linear_value_head_dim=16, linear_conv_kernel_dim=4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    partial_rotary_factor=0.25, rope_theta=1e7, num_experts=16,
    num_experts_per_tok=4, moe_intermediate_size=32,
    shared_expert_intermediate_size=32, norm_topk_prob=True,
    rms_norm_eps=1e-6, num_experts_held=4, first_expert_held=0,
    linear_chunk_size=16, attention="dense")


def _tokens(seed: int, n: int = 2, t: int = T):
    ids = jax.random.randint(jax.random.PRNGKey(seed), (n, t + 1), 1, VOCAB)
    return ids[:, :-1], ids[:, 1:]


@pytest.fixture(scope="module")
def small():
    model = create_model("qwen3_next", **SMALL)
    ids, _ = _tokens(1)
    params = model.init({"params": jax.random.PRNGKey(0)}, ids)["params"]
    # off their initial values, so that every term of every equation counts
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(7), len(leaves))
    params = jax.tree.unflatten(tree, [
        leaf + 0.05 * jax.random.normal(k, leaf.shape)
        for leaf, k in zip(leaves, keys)])
    return model, params


def _highest(fn):
    @functools.wraps(fn)
    def wrapped(*a, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*a, **kw)
    return wrapped


def _close(got, want, rel: float, what="", floor: float = 0.0):
    """Largest difference over the reference's largest entry: float32
    rounding moves small entries of a tensor by the size of its large ones'
    last bits."""
    scale = float(jnp.abs(want).max())
    assert scale > 0, what
    assert float(jnp.abs(got - want).max()) <= rel * scale + floor, what


# --- (a) forward, per kind of layer and end to end -------------------------

@pytest.mark.parametrize("kind", ["gated_deltanet", "gated_attention",
                                  "sparse_moe"])
@_highest
def test_a_layer_kind_equals_the_reference(small, kind):
    _, params = small
    cfg = qn.Qwen3NextShapes(**SMALL)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, T, 64))
    module, layer, sub = {
        "gated_deltanet": (qn.GatedDeltaNet, "layer_0", "mixer"),
        "gated_attention": (qn.GatedAttention, "layer_3", "mixer"),
        "sparse_moe": (qn.SparseMoE, "layer_1", "moe")}[kind]
    p = params[layer][sub]
    got = jax.jit(module(cfg, jnp.float32).apply)({"params": p}, x)
    want = jax.jit(jax.vmap(
        lambda xb: getattr(ref, kind)(p, xb, SMALL)))(x)
    assert float(jnp.abs(want).max()) > 0.05
    _close(got, want, 2e-5)


@_highest
def test_logits_and_loss_equal_the_reference(small):
    model, params = small
    ids, labels = _tokens(2)
    labels = labels.at[:, -5:].set(0)           # pad_id: left out of the mean
    got = jax.jit(model.apply)({"params": params}, ids)
    want = jax.jit(jax.vmap(lambda i: ref.logits(params, i, SMALL)))(ids)
    _close(got, want, 5e-5)
    np.testing.assert_allclose(
        jnp.mean(qn.token_ce(got, labels)),
        jax.jit(lambda: ref.batch_loss(params, ids, labels, SMALL))(),
        rtol=1e-5)
    np.testing.assert_array_equal(qn.token_ce(got, labels),
                                  seq_softmax_ce(got, labels, pad_id=0))


def test_flash_attention_serves_the_attention_core(small):
    """The pallas kernel (interpreted on the CPU) against the dense core."""
    _, params = small
    x = jax.random.normal(jax.random.PRNGKey(3), (1, T, 64))
    p = {"params": params["layer_3"]["mixer"]}

    def out(attention):
        cfg = qn.Qwen3NextShapes(**{**SMALL, "attention": attention})
        fn = lambda x: qn.GatedAttention(cfg, jnp.float32).apply(p, x)
        return jax.jit(lambda x: (
            fn(x), jax.grad(lambda x: jnp.sum(jnp.sin(fn(x))))(x)))(x)

    for got, want in zip(out("flash"), out("dense")):
        _close(got, want, 2e-4)


# --- (b) gradients of every parameter --------------------------------------

@_highest
def test_gradients_of_every_parameter_equal_the_reference(small):
    model, params = small
    ids, labels = _tokens(4)

    def loss(p):
        return jnp.mean(qn.token_ce(model.apply({"params": p}, ids), labels))

    got = jax.jit(jax.grad(loss))(params)
    want = jax.jit(jax.grad(
        lambda p: ref.batch_loss(p, ids, labels, SMALL)))(params)
    flat_got = dict(jax.tree_util.tree_leaves_with_path(got))
    for path, w in jax.tree_util.tree_leaves_with_path(want):
        _close(flat_got[path], w, 1e-3, jax.tree_util.keystr(path))


# --- (c) the chunked delta rule against the token-by-token one -------------

def _delta_inputs(t, seed=0, heads=4, dk=16, dv=16, batch=2):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (batch, t, heads, dk))
    k = jax.random.normal(ks[1], (batch, t, heads, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / jnp.sqrt(dk)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (batch, t, heads, dv))
    g = -0.5 * jnp.exp(jax.random.normal(ks[3], (batch, t, heads)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (batch, t, heads)))
    return q, k, v, g, beta


# heads of 128 x 128 with a chunk of 64 take the Pallas hand-over kernels
# (interpreted on the CPU); everything narrower keeps the scan
KERNEL_SHAPE = dict(heads=2, dk=128, dv=128)


@pytest.mark.parametrize("t, chunk, shape", [
    pytest.param(128, 16, {}, id="128-16"),
    pytest.param(100, 16, {}, id="100-16"),
    pytest.param(192, 64, {}, id="192-64"),
    pytest.param(37, 64, {}, id="37-64"),
    pytest.param(256, 64, KERNEL_SHAPE, id="kernel-256-64"),
    pytest.param(200, 64, KERNEL_SHAPE, id="kernel-200-64"),
    pytest.param(37, 64, KERNEL_SHAPE, id="kernel-37-64")])
@_highest
def test_chunked_delta_rule_forward_and_backward(t, chunk, shape):
    """``t`` not a multiple of the chunk, and shorter than one, included."""
    args = _delta_inputs(t, **shape)
    assert takes_kernel(args[0].shape[-1], args[2].shape[-1], chunk) == bool(
        shape)
    chunked = partial(gated_delta_rule, chunk=chunk)
    _close(jax.jit(chunked)(*args),
           jax.jit(gated_delta_rule_recurrent)(*args), 1e-5)

    def grads(fn):
        return jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a))),
                                argnums=(0, 1, 2, 3, 4)))(*args)

    for got, want in zip(grads(chunked), grads(gated_delta_rule_recurrent)):
        _close(got, want, 2e-5)


def _xla_rule(monkeypatch):
    """``gated_delta_rule`` held to its ``lax.scan``, whatever the shapes."""
    monkeypatch.setattr(gated_delta, "takes_kernel", lambda *shape: False)
    return partial(gated_delta_rule, chunk=64)


def test_the_kernels_equal_the_scan_in_bf16(monkeypatch):
    """Same products in the same dtype, the state float32 on both sides:
    apart by bf16 rounding of differently associated sums and no more."""
    args = _delta_inputs(200, **KERNEL_SHAPE)
    args = tuple(a.astype(jnp.bfloat16) for a in args[:3]) + args[3:]

    def both(fn):
        out, vjp = jax.vjp(fn, *args)
        return (out,) + vjp(jnp.ones_like(out))

    got = jax.jit(partial(both, partial(gated_delta_rule, chunk=64)))()
    want = jax.jit(partial(both, _xla_rule(monkeypatch)))()
    for g, w, what in zip(got, want, ("o", "dq", "dk", "dv", "dg", "dbeta")):
        assert g.dtype == w.dtype, what
        _close(g.astype(jnp.float32), w.astype(jnp.float32), 2 ** -6, what)


@_highest
def test_the_kernels_under_vmap_and_checkpoint():
    """What the round does to the rule: ``nn.remat`` around the layer and
    (in the vmapped round) a leading client axis."""
    clients = [_delta_inputs(100, seed=s, batch=1, **KERNEL_SHAPE)
               for s in (0, 1)]
    args = tuple(jnp.stack(a) for a in zip(*clients))

    def loss(fn, *a):
        rule = jax.vmap(jax.checkpoint(fn))
        return jnp.sum(jnp.sin(rule(*a)))

    def grads(fn):
        return jax.jit(jax.value_and_grad(
            partial(loss, fn), argnums=(0, 1, 2, 3, 4)))(*args)

    (got, dgot), (want, dwant) = (grads(partial(gated_delta_rule, chunk=64)),
                                  grads(gated_delta_rule_recurrent))
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for g, w in zip(dgot, dwant):
        _close(g, w, 2e-5)


@_highest
def test_the_inverse_has_its_own_derivative():
    """``dA = -T^T dT T^T`` (the kernel path) against JAX's transpose of the
    series (the scan's), on strictly lower triangular chunks."""
    a = jnp.tril(0.3 * jax.random.normal(jax.random.PRNGKey(3), (3, 64, 64)),
                 -1)
    cot = jax.random.normal(jax.random.PRNGKey(4), a.shape)

    def grad(inverse):
        return jax.jit(jax.grad(lambda x: jnp.sum(inverse(x) * cot)))(a)

    want = grad(gated_delta._inverse_unit_lower)
    _close(grad(gated_delta._inverse_unit_lower_saved), want, 1e-5)


def test_the_kernels_are_chosen_from_the_shapes_alone():
    """The published heads take the kernels, the tests' model the scan; the
    traced program says which (``pallas_call`` forward, and forward with
    residuals + backward under ``grad``)."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "qwen3_next_80b_a3b.json")) as f:
        published = json.load(f)["factory_kwargs"]
    cfg = qn.qwen3_next(**published).cfg
    assert takes_kernel(cfg.linear_key_head_dim, cfg.linear_value_head_dim,
                        cfg.linear_chunk_size)
    assert not takes_kernel(SMALL["linear_key_head_dim"],
                            SMALL["linear_value_head_dim"],
                            SMALL["linear_chunk_size"])

    def calls(dk, dv, chunk):
        args = _delta_inputs(2 * chunk, heads=2, dk=dk, dv=dv, batch=1)
        rule = partial(gated_delta_rule, chunk=chunk)
        loss = lambda *a: jnp.sum(rule(*a))
        return (str(jax.make_jaxpr(rule)(*args)).count("pallas_call"),
                str(jax.make_jaxpr(jax.grad(loss))(*args)).count(
                    "pallas_call"))

    assert calls(cfg.linear_key_head_dim, cfg.linear_value_head_dim,
                 cfg.linear_chunk_size) == (1, 2)
    assert calls(SMALL["linear_key_head_dim"], SMALL["linear_value_head_dim"],
                 SMALL["linear_chunk_size"]) == (0, 0)
    assert calls(128, 64, 64) == calls(96, 128, 64) == (0, 0)


# --- (d) the shares add up -------------------------------------------------

@_highest
def _counted(cfg, params, x):
    """One call of the expert layer from zeroed counters: ``(output, the
    ``counters`` collection after it)``."""
    layer = qn.SparseMoE(cfg, jnp.float32)
    zero = jax.tree.map(jnp.zeros_like, layer.init(
        jax.random.PRNGKey(0), x)["counters"])
    out, state = layer.apply({"params": params, "counters": zero}, x,
                             mutable=["counters"])
    return out, jax.tree.map(np.asarray, state["counters"])


def test_the_shares_of_the_expert_layer_add_up(small):
    """All 4 shares of 4 experts, each given its own experts' weights, the
    shared expert counted once: the uncut layer of 16 experts."""
    rng = jax.random.split(jax.random.PRNGKey(11), 8)
    d, f, experts = 64, 32, 16
    whole = {
        "router": jax.random.normal(rng[0], (d, experts)),
        "experts_gate_up": 0.2 * jax.random.normal(rng[1], (experts, d, 2 * f)),
        "experts_down": 0.2 * jax.random.normal(rng[2], (experts, f, d)),
        "shared_gate_up": 0.2 * jax.random.normal(rng[3], (d, 2 * f)),
        "shared_down": 0.2 * jax.random.normal(rng[4], (f, d)),
        "shared_gate": jax.random.normal(rng[5], (d, 1))}
    x = jax.random.normal(rng[6], (1, T, d))
    uncut = jax.jit(lambda: ref.sparse_moe(
        whole, x[0], {**SMALL, "first_expert_held": 0}))()
    shared = ref.sparse_moe(
        {**whole, "experts_gate_up": whole["experts_gate_up"][:0],
         "experts_down": whole["experts_down"][:0]}, x[0], SMALL)
    total, tokens, unrouted = shared, 0, []
    for first in range(0, experts, 4):
        cfg = qn.Qwen3NextShapes(**{**SMALL, "first_expert_held": first})
        mine = {**whole,
                "experts_gate_up": whole["experts_gate_up"][first:first + 4],
                "experts_down": whole["experts_down"][first:first + 4]}
        out, counted = _counted(cfg, mine, x)
        assert counted["dense_arm_calls"] == 0
        total = total + (out[0] - shared)       # the routed part of a share
        tokens += int(counted["expert_tokens"].sum())
        unrouted.append(int(counted["unrouted_tokens"]))
    _close(total, uncut, 2e-5)
    assert tokens == T * 4                      # nothing dropped, anywhere
    assert all(0 < u < T for u in unrouted)


@_highest
def test_an_overloaded_expert_takes_the_dense_arm_and_drops_nothing(small):
    """Every token chooses the four held experts (four times their share:
    more assignments than the layout has rows): the layer computes every
    held expert over every token instead, and still equals the reference."""
    _, params = small
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(3), (1, T, 64)))
    p = dict(params["layer_1"]["moe"])
    p["router"] = p["router"].at[:, :4].set(1.0)
    out, counted = _counted(qn.Qwen3NextShapes(**SMALL), p, x)
    assert counted["dense_arm_calls"] == 1
    assert counted["uncomputed_tokens"] == 0
    _close(out[0], ref.sparse_moe(p, x[0], SMALL), 2e-5)


@_highest
def test_one_popular_expert_does_not_overflow_the_layout(small):
    """Every token chooses held expert 0 (four times the mean load): its
    tokens take the tiles they need, the total still fits, and the grouped
    arm equals the reference."""
    _, params = small
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(3), (1, T, 64)))
    p = dict(params["layer_1"]["moe"])
    p["router"] = p["router"].at[:, 0].set(1.0)
    out, counted = _counted(qn.Qwen3NextShapes(**SMALL), p, x)
    assert counted["expert_tokens"][0] == T
    assert counted["dense_arm_calls"] == 0
    assert counted["uncomputed_tokens"] == 0
    _close(out[0], ref.sparse_moe(p, x[0], SMALL), 2e-5)


def test_a_second_precision_in_one_process_reaches_every_layer(small,
                                                              monkeypatch):
    """The reference's lower-precision reading (``PRODUCT_BITS``) with its
    blocks and checkpoints on, asked for AFTER the float32 one in the same
    process: the first layer's gradient has to move by what 4-bit products
    cost, not by the head's share alone (``jax.checkpoint`` keeps the trace
    of a function it has seen; the control of PR 28's first chip runs
    rounded the head only and read 1.2-1.5 times bf16 for it)."""
    _, params = small
    ids, labels = _tokens(5)
    cfg = {**SMALL, "token_block": 32}

    def grad(bits):
        monkeypatch.setattr(ref, "PRODUCT_BITS", bits)
        return ref.loss_and_grad(cfg)(params, ids, labels)[1]

    exact, rounded = grad(None), grad(4)
    for name in ("in_proj_qkvz", "out_proj"):
        a, b = (g["layer_0"]["mixer"][name] for g in (rounded, exact))
        assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) > 0.1


# --- (e) one round through FedAvgAPI equals the reference round ------------

def _federation(clients=3, per_client=2):
    ids, labels = _tokens(5, n=clients * per_client)
    parts = {c: np.arange(c * per_client, (c + 1) * per_client)
             for c in range(clients)}
    return (np.asarray(ids), np.asarray(labels), parts,
            build_federated_arrays(np.asarray(ids), np.asarray(labels),
                                   parts, 1))


def _api(fed, clients, dtype="fp32", k=1, lr=0.1, epochs=1):
    cfg = FedConfig(client_num_in_total=clients, client_num_per_round=clients,
                    comm_round=10, epochs=epochs, batch_size=1, lr=lr, seed=2,
                    client_group_size=k, remat=True, client_step_dtype=dtype)
    return FedAvgAPI(create_model("qwen3_next", **SMALL), fed, None, cfg,
                     loss_fn=partial(seq_softmax_ce, pad_id=0))


def _reference_round(params, ids, labels, parts, lr, epochs=1):
    """The round's clients in the slot order ``FedAvgAPI`` trains them, each
    client's sequences in their stored order (the round shuffles them; with
    2 steps of batch 1 either order is a permutation of the same two steps,
    so the reference is given the order the round drew)."""
    clients = [([(ids[i][None], labels[i][None]) for i in parts[c]],
                len(parts[c])) for c in sorted(parts)]
    return ref.fedavg_round(params, clients, SMALL, lr, epochs=epochs)


def test_one_round_through_fedavg_equals_the_reference_round():
    """``k = 1``, remat on, one sequence a client so that no shuffle of the
    round can reorder the steps."""
    ids, labels, parts, fed = _federation(clients=3, per_client=1)
    with jax.default_matmul_precision("highest"):
        api = _api(fed, 3)
        start = jax.tree.map(jnp.array, api.net.params)
        loss = api.train_one_round(0)["train_loss"]
        want, want_loss = _reference_round(start, ids, labels, parts, 0.1)
    assert loss == pytest.approx(want_loss, rel=1e-5)
    # the round's own program counted: every client one step of T tokens,
    # top-4 of 16 experts with 4 held (the cohort's mean: T * 4 chosen a
    # layer, the held ones and nothing uncomputed among them)
    for layer in api.net.model_state["counters"].values():
        moe = jax.tree.map(np.asarray, layer["moe"])
        assert 0 < moe["expert_tokens"].sum() < 4 * T
        assert 0 < moe["unrouted_tokens"] < T
        assert moe["uncomputed_tokens"] == 0 and moe["dense_arm_calls"] == 0
    got = dict(jax.tree_util.tree_leaves_with_path(api.net.params))
    first = dict(jax.tree_util.tree_leaves_with_path(start))
    for path, w in jax.tree_util.tree_leaves_with_path(want):
        # an update is known to the last bit of the parameter it moved
        ulp = 2.0 ** -23 * float(jnp.abs(first[path]).max())
        _close(got[path] - first[path], w - first[path], 2e-3,
               jax.tree_util.keystr(path), floor=2 * ulp)


# --- (g) the bf16 step inside the chip comparison's tolerances -------------

def _runner():
    return _load_benchmark(os.path.join("runners", "fed_lm_round.py"),
                           "bench_fed_lm_round")


def test_the_bf16_step_is_inside_the_chip_comparisons_tolerances():
    """The comparison that decides ``correct`` on the chip
    (``benchmark/runners/fed_lm_round.compare_update`` and ``TOLERANCES``),
    here at the small size: the bf16 client step, two local steps a client
    as in the cell, against the float32 reference round. Every limit holds,
    and none is looser than ``ROOM`` times the error this test measures, so
    a limit cannot drift away from what bf16 costs. ``ROOM`` is 12, not 4:
    a limit is about 3 times the largest error read on the chip, and at the
    published widths bf16 costs up to 3 times what it costs at hidden size
    64 (0.07-0.08 against 0.024-0.045 on the kinds under the attention
    layer; PERF.md section 6, PR 28)."""
    room = 12
    runner = _runner()
    ids, labels, parts, fed = _federation(clients=2, per_client=1)
    api = _api(fed, 2, dtype="bf16", lr=0.5, epochs=2)
    start = jax.tree.map(jnp.array, api.net.params)
    loss = api.train_one_round(0)["train_loss"]
    want, want_loss = _reference_round(start, ids, labels, parts, 0.5,
                                       epochs=2)
    errors = runner.compare_update(start, api.net.params, want)
    errors["loss"] = abs(loss - want_loss)
    assert set(errors) == set(runner.TOLERANCES)
    for kind, err in errors.items():
        limit = runner.TOLERANCES[kind]["limit"]
        assert err <= limit, (kind, err, limit)
        if kind != "loss":      # an absolute difference that may read 0
            assert limit <= room * err, (kind, err, limit)
