"""Granite 4.0-H (``models/granite_hybrid.py``) against its plain reference
(``benchmark/reference_granite_hybrid.py``) on seeded weights, the flash path
at its head size and scale under ``vmap``, and the federated adapter round
with the frozen base as an operand. CPU, small sizes."""

import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.algos.config import FedConfig
from fedml_tpu.algos.fedadapter import FedAdapterAPI
from fedml_tpu.data.batching import build_federated_arrays
from fedml_tpu.models.adapter import merge_params, split_frozen
from fedml_tpu.models.granite_hybrid import (GraniteHybridShapes,
                                             granite_hybrid, token_ce)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the source's keys at the CPU tests' sizes: hidden 64, 2 periods of
#: (2 Mamba-2, 1 attention, 1 Mamba-2), 4 heads of 16, state 16, chunk 8
SMALL = dict(
    vocab_size=257, hidden_size=64, num_hidden_layers=8,
    layer_types=["mamba", "mamba", "attention", "mamba"] * 2,
    intermediate_size=96, shared_intermediate_size=96, mamba_n_heads=8,
    mamba_d_head=16, mamba_d_state=16, mamba_chunk_size=8,
    num_attention_heads=4, num_key_value_heads=2, attention_multiplier=0.0625,
    adapter_rank=4, adapter_alpha=8.0, adapter_b_std=0.01)
#: the rest of the source's dictionary, as published
PUBLISHED = dict(
    attention_bias=False, embedding_multiplier=12, hidden_act="silu",
    logits_scaling=8, mamba_conv_bias=True, mamba_d_conv=4, mamba_expand=2,
    mamba_n_groups=1, mamba_proj_bias=False, max_position_embeddings=131072,
    model_type="granitemoehybrid", normalization_function="rmsnorm",
    num_experts_per_tok=0, num_local_experts=0,
    position_embedding_type="nope", residual_multiplier=0.22,
    rms_norm_eps=1e-05, rope_scaling=None, rope_theta=10000,
    tie_word_embeddings=True)
CFG = {**PUBLISHED, **SMALL}
T = 24


def _reference():
    spec = importlib.util.spec_from_file_location(
        "test_reference_granite_hybrid",
        os.path.join(ROOT, "benchmark", "reference_granite_hybrid.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def reference():
    return _reference()


@pytest.fixture(scope="module")
def seeded():
    """``(model, base, adapters, ids, labels)`` on seeded weights."""
    model = granite_hybrid(**CFG, attention="flash")
    ids = jax.random.randint(jax.random.PRNGKey(0), (2, T), 1, 257)
    labels = jnp.concatenate(
        [ids[:, 1:], jnp.zeros((2, 1), jnp.int32)], axis=1)
    params = jax.jit(lambda r, x: model.init({"params": r}, x))(
        jax.random.PRNGKey(1), ids)["params"]
    base, adapters = split_frozen(params)
    return model, base, adapters, ids, labels


def _loss_and_grad(model, base, adapters, ids, labels):
    def loss(a):
        logits = model.apply({"params": merge_params(base, a)}, ids)
        return jnp.mean(token_ce(logits, labels))

    return jax.value_and_grad(loss)(adapters)


def _relative(got, want):
    num = sum(float(jnp.sum((g - w) ** 2)) for g, w in zip(
        jax.tree.leaves(got), jax.tree.leaves(want)))
    den = sum(float(jnp.sum(w ** 2)) for w in jax.tree.leaves(want))
    return (num / den) ** 0.5


# --- the model against the reference ----------------------------------------

def test_the_base_is_bf16_and_the_adapters_float32(seeded):
    _, base, adapters, _, _ = seeded
    assert {a.dtype for a in jax.tree.leaves(base)} == {jnp.dtype("bfloat16")}
    assert {a.dtype for a in jax.tree.leaves(adapters)} == {
        jnp.dtype("float32")}
    # a period's layers are stacked: 2 periods of 4 layers
    assert set(base["periods"]) == {f"layer_{j}" for j in range(4)}
    assert base["periods"]["layer_2"]["mixer"]["q_proj"].shape == (2, 64, 64)
    assert adapters["periods"]["layer_0"]["mixer"][
        "lora_in_proj_b"].shape == (2, 4, 128 + 160 + 8)
    assert float(jnp.abs(adapters["periods"]["layer_0"]["mlp"][
        "lora_output_linear_b"]).max()) > 0        # adapter_b_std


@pytest.mark.parametrize("token_block", [None, 8])
def test_float32_logits_loss_and_adapter_gradients(seeded, reference,
                                                   token_block):
    """The model in float32 against the token-by-token, dense-attention
    reference (whole, and in blocks of tokens as the chip runs it): 1e-5."""
    model, base, adapters, ids, labels = seeded
    cfg = dict(CFG, token_block=token_block) if token_block else dict(CFG)
    with jax.default_matmul_precision("highest"):
        logits = model.apply({"params": merge_params(base, adapters)}, ids)
        want = jnp.stack([reference.logits(base, adapters, ids[b], cfg)
                          for b in range(2)])
        loss, grads = _loss_and_grad(model, base, adapters, ids, labels)
        want_loss, want_grads = reference.loss_and_grad(
            dict(cfg, base=base))(adapters, ids, labels)
    np.testing.assert_allclose(logits, want, atol=1e-5 * float(
        jnp.abs(want).max()))
    assert float(loss) == pytest.approx(float(want_loss), abs=1e-5)
    assert _relative(grads, want_grads) < 1e-5
    flat = jax.tree.leaves(want_grads)
    assert all(float(jnp.abs(g).max()) > 0 for g in flat)   # B != 0: A binds


#: the bf16 step against the float32 reference at these sizes: 8 layers of
#: bf16 products read 0.4-0.6 % on the adapters' gradient (measured here);
#: the limit leaves three times that, and the reference with float8's 4-bit
#: products in the program's place reads over ten times the limit.
BF16_GRADIENT_LIMIT = 0.02


def test_bf16_step_against_the_reference_and_the_4_bit_control(seeded,
                                                               reference):
    model, base, adapters, ids, labels = seeded
    step = model.clone(dtype=jnp.bfloat16)
    loss, grads = _loss_and_grad(step, base, adapters, ids, labels)
    with jax.default_matmul_precision("highest"):
        want_loss, want = reference.loss_and_grad(dict(CFG, base=base))(
            adapters, ids, labels)
        reference.PRODUCT_BITS = 4
        try:
            _, control = reference.loss_and_grad(dict(CFG, base=base))(
                adapters, ids, labels)
        finally:
            reference.PRODUCT_BITS = None
    assert float(loss) == pytest.approx(float(want_loss), abs=2e-3)
    assert _relative(grads, want) < BF16_GRADIENT_LIMIT
    assert _relative(control, want) > 2 * BF16_GRADIENT_LIMIT


def test_dense_attention_is_the_flash_path(seeded):
    model, base, adapters, ids, _ = seeded
    dense = granite_hybrid(**CFG, attention="dense")
    params = {"params": merge_params(base, adapters)}
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(model.apply(params, ids),
                                   dense.apply(params, ids), atol=1e-6)


def test_flash_at_head_64_and_scale_1_over_64_under_vmap():
    """Granite's attention core: head 64, 32 query heads over 8 key-value
    heads repeated, ``attention_multiplier`` 1/64 (not 1/8), causal, no
    positions, batched over clients by ``vmap``: the kernel (interpreted
    here) against dense softmax attention, output and gradients."""
    from fedml_tpu.ops.flash_attention import flash_attention

    clients, t, hq, hkv, hd, scale = 3, 32, 8, 2, 64, 0.015625
    k0, k1, k2 = jax.random.split(jax.random.PRNGKey(2), 3)
    q = 4.0 * jax.random.normal(k0, (clients, 1, t, hq, hd))
    k = jnp.repeat(4.0 * jax.random.normal(k1, (clients, 1, t, hkv, hd)),
                   hq // hkv, axis=3)
    v = jnp.repeat(jax.random.normal(k2, (clients, 1, t, hkv, hd)),
                   hq // hkv, axis=3)

    def dense(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, scale=scale,
                               block_q=16, block_k=16)

    def through(fn):
        return jax.vmap(jax.value_and_grad(
            lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=(0, 1, 2)))

    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(jax.vmap(flash)(q, k, v),
                                   jax.vmap(dense)(q, k, v), atol=2e-5)
        (_, got), (_, want) = through(flash)(q, k, v), through(dense)(q, k, v)
        # the default scale is another function: 1/8 at head 64
        other = jax.vmap(lambda *a: flash_attention(*a, causal=True))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-5 * float(jnp.abs(w).max()))
    assert float(jnp.abs(other - jax.vmap(dense)(q, k, v)).max()) > 1e-2


def test_the_factory_takes_the_sources_keys_and_refuses_what_it_cannot_run():
    assert granite_hybrid(**CFG).cfg.period == (
        "mamba", "mamba", "attention", "mamba")
    assert GraniteHybridShapes().period == (
        ("mamba",) * 5 + ("attention",) + ("mamba",) * 4)
    assert len(GraniteHybridShapes().layer_types) == 40
    with pytest.raises(NotImplementedError, match="position_embedding_type"):
        granite_hybrid(**{**CFG, "position_embedding_type": "rope"})
    with pytest.raises(NotImplementedError, match="num_local_experts"):
        granite_hybrid(**{**CFG, "num_local_experts": 8})
    with pytest.raises(TypeError, match="unknown keys"):
        granite_hybrid(**CFG, mamba_typo=1)
    with pytest.raises(ValueError, match="layer_types"):
        granite_hybrid(**{**CFG, "num_hidden_layers": 6})
    from fedml_tpu.models.registry import create_model

    assert create_model("granite_hybrid", **CFG).cfg.hidden_size == 64


# --- the federated adapter round --------------------------------------------

CLIENTS, PER_CLIENT, BATCH = 4, 2, 2


@pytest.fixture(scope="module")
def federation():
    rng = np.random.default_rng(5)
    x = rng.integers(1, 257, (CLIENTS * PER_CLIENT, T)).astype(np.int32)
    y = np.concatenate([x[:, 1:], np.zeros((len(x), 1), np.int32)], axis=1)
    parts = {c: np.arange(c * PER_CLIENT, (c + 1) * PER_CLIENT)
             for c in range(CLIENTS)}
    return x, y, parts


def _api(federation, model_cfg=CFG, **cfg_more):
    x, y, parts = federation
    fed = build_federated_arrays(x, y, parts, BATCH)
    cfg = FedConfig(client_num_in_total=CLIENTS, client_num_per_round=CLIENTS,
                    comm_round=4, epochs=2, batch_size=BATCH,
                    client_optimizer="sgd", lr=0.5, seed=7, **cfg_more)
    return FedAdapterAPI(granite_hybrid(**model_cfg, attention="flash"), fed,
                         None, cfg, loss_fn=token_ce)


def test_one_round_of_four_clients_is_the_references_round(federation,
                                                           reference):
    """``FedAdapterAPI.train_one_round`` (4 clients under one vmap, 2 local
    steps each, float32) against ``reference.fedavg_round`` from the same
    base and adapters; and the base is bit for bit what it was."""
    x, y, parts = federation
    with jax.default_matmul_precision("highest"):
        api = _api(federation)
        base0 = jax.tree.map(np.asarray, api.base)
        start = jax.tree.map(jnp.asarray, jax.tree.map(
            np.asarray, api.net.params))
        loss = api.train_one_round(0)["train_loss"]
        clients = [([(x[parts[c]], y[parts[c]])], PER_CLIENT)
                   for c in range(CLIENTS)]
        want, want_loss = reference.fedavg_round(
            start, clients, dict(CFG, base=api.base), lr=0.5, epochs=2)
    assert loss == pytest.approx(want_loss, abs=1e-5)
    update = jax.tree.map(lambda a, b: a - b, api.net.params, start)
    wanted = jax.tree.map(lambda a, b: a - b, want, start)
    assert _relative(update, wanted) < 1e-4
    for a, b in zip(jax.tree.leaves(base0), jax.tree.leaves(api.base)):
        np.testing.assert_array_equal(a, np.asarray(b))


def test_the_base_is_an_operand_of_the_round_not_its_constant(federation):
    """The lowered fused round takes every base tensor as an argument and
    holds no literal of a base tensor's size (at the published sizes: none
    over 1 MB, where the base is 6.4 GB); the counters say what it is
    handed and what the rounds fold."""
    api = _api(federation, client_step_dtype="bf16")
    _, gather = api._fused_round_step()
    idx, wmask = api.sample_round(0)
    lowered = gather.lower(api.net, api._window_carry_init(), api.train_fed,
                           jnp.asarray(idx), jnp.asarray(wmask), api.rng)
    text = lowered.as_text()
    base_leaves = jax.tree.leaves(api.base)
    n_args = len(jax.tree.leaves(lowered.args_info))
    assert n_args >= len(base_leaves) + len(jax.tree.leaves(api.net.params))
    smallest_matrix = min(a.size for a in base_leaves if a.ndim >= 3)
    sizes = [int(np.prod([int(d) for d in dims.split("x") if d]))
             for dims in re.findall(
                 r"stablehlo\.constant dense<[^>]*> : tensor<((?:\d+x)*)\w+>",
                 text)] or [0]
    assert max(sizes) < smallest_matrix, max(sizes)
    assert len(text) < 1 << 20
    profile = api.adapter_profile()
    assert profile["base_bytes_operand"] == 2 * profile["base_params"]
    assert profile.get("adapter_bytes_folded", 0) == 0
    api.train_one_round(0)
    api.train_one_round(1)
    after = api.adapter_profile()
    assert "adapter_rounds" not in after
    # 6 Mamba-2 layers x 2 + 2 attention layers x 4 + 8 MLPs x 2 projections
    # go through ops.lora_linear; at a depth of 64 none takes its kernel
    assert (after["lora_sites"], after["lora_sites_fused"]) == (36, 0)
    assert after["adapter_bytes_folded"] == (
        2 * CLIENTS * 4 * after["adapter_params"])


#: a width whose projections fill whole lanes: hidden 128, 2 periods of
#: (Mamba-2, attention), 4 heads of 64 / 32, an MLP of 256
WIDE = dict(CFG, hidden_size=128, num_hidden_layers=4,
            layer_types=["mamba", "attention"] * 2, intermediate_size=256,
            shared_intermediate_size=256, mamba_n_heads=4, mamba_d_head=64)


def test_a_width_that_takes_the_kernel_is_the_references_round(
        federation, reference, monkeypatch):
    """Where the shapes take ``ops/lora_linear``'s kernel (here the gated
    ``input_linear``, as in the benchmark's cell, and ``in_proj`` with its
    partial last tile; the least result lowered to this width's) the model
    and the round of four clients under one vmap are still the reference's,
    in float32 to 1e-5, and ``adapter_profile`` counts the sites so."""
    from fedml_tpu.ops import lora_linear as ll

    rows = BATCH * T
    monkeypatch.setattr(ll, "MIN_RESULT", rows * 512)
    fused = {"in_proj": (128, 256 + 288 + 4), "input_linear": (128, 512)}
    plain = {"out_proj": (256, 128), "output_linear": (256, 128),
             "q_proj": (128, 128), "k_proj": (128, 64)}
    assert all(ll.takes_kernel(rows, k, n, 4, name == "input_linear")
               for name, (k, n) in fused.items())
    assert not any(ll.takes_kernel(rows, k, n, 4) for k, n in plain.values())

    x, y, parts = federation
    model = granite_hybrid(**WIDE, attention="flash")
    with jax.default_matmul_precision("highest"):
        api = _api(federation, WIDE)
        start = jax.tree.map(jnp.asarray, jax.tree.map(
            np.asarray, api.net.params))
        ids, labels = jnp.asarray(x[:BATCH]), jnp.asarray(y[:BATCH])
        loss, grads = _loss_and_grad(model, api.base, start, ids, labels)
        want_loss, want_grads = reference.loss_and_grad(
            dict(WIDE, base=api.base))(start, ids, labels)
        round_loss = api.train_one_round(0)["train_loss"]
        clients = [([(x[parts[c]], y[parts[c]])], PER_CLIENT)
                   for c in range(CLIENTS)]
        want, want_round_loss = reference.fedavg_round(
            start, clients, dict(WIDE, base=api.base), lr=0.5, epochs=2)
    assert float(loss) == pytest.approx(float(want_loss), abs=1e-5)
    assert _relative(grads, want_grads) < 1e-5
    assert round_loss == pytest.approx(want_round_loss, abs=1e-5)
    update = jax.tree.map(lambda a, b: a - b, api.net.params, start)
    wanted = jax.tree.map(lambda a, b: a - b, want, start)
    assert _relative(update, wanted) < 1e-4
    # 2 Mamba-2 layers x 2 + 2 attention layers x 4 + 4 MLPs x 2; of them
    # 2 in_proj and 4 input_linear take the kernel
    profile = api.adapter_profile()
    assert (profile["lora_sites"], profile["lora_sites_fused"]) == (20, 6)
    _, gather = api._fused_round_step()
    idx, wmask = api.sample_round(0)
    text = str(gather._jitted.trace(
        api.base, api.net, api._window_carry_init(), api.train_fed,
        jnp.asarray(idx), jnp.asarray(wmask), api.rng).jaxpr)
    # the first forward writes outputs only; the recomputed one the gate's sum
    for name in ("lora_linear", "lora_linear_gate", "lora_linear_gate_saved"):
        assert f"name={name}\n" in text or f"name={name} " in text, name


def test_the_folded_bytes_count_the_clients_that_had_weight(federation):
    """``adapter_bytes_folded`` is counted from the round's weights: a
    client with no samples is sampled, trains padding at weight 0 and
    uploads nothing."""
    x, y, parts = federation
    parts = {**parts, 3: parts[3][:0]}
    api = _api((x, y, parts))
    api.train_one_round(0)
    profile = api.adapter_profile()
    assert profile["adapter_bytes_folded"] == (
        (CLIENTS - 1) * 4 * profile["adapter_params"])


def test_a_seeded_base_is_handed_in_and_held_as_it_is(federation, reference):
    """``base_params``: the reference's ``init_base`` tree has the model's
    own structure, shapes and dtype and follows the ``assumed`` laws; the
    API holds those very arrays, makes the adapters alone (the fresh
    init's bit for bit), and the round leaves the base as it was."""
    kwargs = {**CFG, "base_dtype": "bfloat16"}
    weights = reference.init_base(kwargs, 3200000555)
    again = reference.init_base(kwargs, 3200000555)
    other = reference.init_base(kwargs, 3200000556)
    fresh = _api(federation)
    assert jax.tree.structure(weights) == jax.tree.structure(fresh.base)
    for a, b, c, d in zip(*map(jax.tree.leaves,
                               (weights, fresh.base, again, other))):
        assert a.shape == b.shape and a.dtype == b.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
    assert not np.array_equal(np.asarray(weights["embed"]),
                              np.asarray(other["embed"]))
    f32 = lambda a: np.asarray(a.astype(jnp.float32))  # noqa: E731
    mamba = weights["periods"]["layer_0"]["mixer"]
    assert mamba["A_log"].shape == (2, SMALL["mamba_n_heads"])
    assert np.all((1 <= np.exp(f32(mamba["A_log"])))
                  & (np.exp(f32(mamba["A_log"])) <= 16.1))
    dt = np.logaddexp(0, f32(mamba["dt_bias"]))
    assert np.all((0.9e-3 <= dt) & (dt <= 0.11))
    assert np.all(f32(mamba["D"]) == 1) and np.all(
        np.abs(f32(mamba["conv_weight"])) <= 0.5)
    assert abs(float(np.std(f32(weights["embed"]))) - 0.02) < 2e-3
    # two layers of one kind, and two periods of one layer, differ
    assert not np.array_equal(f32(mamba["in_proj"][0]), f32(mamba["in_proj"][1]))
    assert "q_proj" in weights["periods"]["layer_2"]["mixer"]

    x, y, parts = federation
    fed = build_federated_arrays(x, y, parts, BATCH)
    cfg = FedConfig(client_num_in_total=CLIENTS, client_num_per_round=CLIENTS,
                    comm_round=4, epochs=2, batch_size=BATCH,
                    client_optimizer="sgd", lr=0.5, seed=7)
    api = FedAdapterAPI(granite_hybrid(**CFG, attention="flash"), fed, None,
                        cfg, loss_fn=token_ce, base_params=weights)
    for a, b in zip(jax.tree.leaves(api.base), jax.tree.leaves(weights)):
        assert a is b
    for a, b in zip(jax.tree.leaves(api.net.params),
                    jax.tree.leaves(fresh.net.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    before = jax.tree.map(np.asarray, weights)
    assert np.isfinite(api.train_one_round(0)["train_loss"])
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(api.base)):
        np.testing.assert_array_equal(a, np.asarray(b))


def test_a_large_base_is_refused_outside_bind(monkeypatch):
    """``apply`` outside ``bind`` would make the base a program's constants:
    above ``BAKED_BASE_LIMIT`` it refuses, so a jit that misses the operand
    fails where it is built; inside ``bind`` the same call runs."""
    from fedml_tpu.models import adapter

    model = granite_hybrid(**CFG, attention="dense")
    fns = adapter.adapter_model_fns(model)
    ids = jnp.ones((1, 8), jnp.int32)
    net = fns.init(jax.random.PRNGKey(0), ids)
    logits, _ = jax.jit(lambda n, x: fns.apply(n, x))(net, ids)  # small: baked
    monkeypatch.setattr(adapter, "BAKED_BASE_LIMIT", 1 << 10)
    with pytest.raises(ValueError, match="outside bind"):
        jax.jit(lambda n, x: fns.apply(n, x))(net, ids + 0)
    bound, _ = jax.jit(fns.bind(lambda n, x: fns.apply(n, x)))(
        fns.base(), net, ids)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(bound), atol=1e-6)
