"""K-EXAONE (``models/k_exaone.py``) against its plain reference
(``benchmark/reference_k_exaone.py``) on seeded weights: window, full and
dense layers, the sigmoid router with its selection bias, the shares of an
expert-parallel layer summed, the held experts' product for frozen experts
with a pair a client under ``vmap``, and the federated adapter round carrying
the model's counters with the frozen base as an operand. CPU, small sizes."""

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
from fedml_tpu.models.k_exaone import (KExaoneShapes, SparseMoE, k_exaone,
                                       token_ce)
from fedml_tpu.parallel import expert_parallel as ep

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the source's keys at the CPU tests' sizes: hidden 32, the dense layer and
#: one period (window, window, FULL, window) of expert layers, a window of 6,
#: 4 query heads over 2 key-value heads of 8, 16 experts top-3 of which 4
#: (experts 4-7) are held
SMALL = dict(
    vocab_size=97, hidden_size=32, num_hidden_layers=5, sliding_window=6,
    num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    intermediate_size=48, moe_intermediate_size=16, num_experts=16,
    num_experts_per_tok=3, num_experts_held=4, first_expert_held=4,
    adapter_rank=4, adapter_alpha=8.0, adapter_b_std=0.01)
#: the rest of the source's dictionary, as published (the lists whole: the
#: factory takes the first ``num_hidden_layers`` entries)
PUBLISHED = dict(
    first_k_dense_replace=1, hidden_act="silu",
    layer_types=(["sliding_attention"] * 3 + ["full_attention"]) * 12,
    max_position_embeddings=262144,
    mlp_layer_types=["dense"] + ["sparse"] * 47, model_type="exaone_moe",
    mtp_layer_types=["full_attention"], mtp_sliding_windows=[0], n_group=1,
    norm_topk_prob=True, num_nextn_predict_layers=1, num_shared_experts=1,
    rms_norm_eps=1e-05,
    rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
    routed_scaling_factor=2.5, scoring_func="sigmoid",
    sliding_window_pattern="LLLG", sliding_windows=[128, 128, 128, 0] * 12,
    tie_word_embeddings=False, topk_group=1)
CFG = {**PUBLISHED, **SMALL}
T = 24


def _reference():
    spec = importlib.util.spec_from_file_location(
        "test_reference_k_exaone",
        os.path.join(ROOT, "benchmark", "reference_k_exaone.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def reference():
    return _reference()


def _spread_router(base, factor=12.0):
    """The toy width's router scores all lie near 1/2 (32 inputs of scale
    0.02), so the bias alone would select: scale the router up so that the
    scores spread as the published width's do."""
    out = jax.tree.map(lambda a: a, base)
    for name, layer in out.items():
        if isinstance(layer, dict) and "moe" in layer:
            layer["moe"] = dict(layer["moe"],
                                router=layer["moe"]["router"] * factor)
    return out


@pytest.fixture(scope="module")
def seeded():
    """``(model, base, adapters, ids, labels)`` on seeded float32 weights."""
    model = k_exaone(**CFG, base_dtype="float32", attention="flash")
    ids = jax.random.randint(jax.random.PRNGKey(0), (2, T), 1, 97)
    labels = jnp.concatenate(
        [ids[:, 1:], jnp.zeros((2, 1), jnp.int32)], axis=1)
    params = jax.jit(lambda r, x: model.init({"params": r}, x))(
        jax.random.PRNGKey(1), ids)["params"]
    base, adapters = split_frozen(params)
    return model, _spread_router(base), adapters, ids, labels


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

def test_the_tree_holds_what_the_configuration_says(seeded):
    _, base, adapters, _, _ = seeded
    assert set(base) == {"embed", "final_norm", "lm_head"} | {
        f"layer_{i}" for i in range(5)}
    assert "mlp" in base["layer_0"] and "moe" not in base["layer_0"]
    moe = base["layer_3"]["moe"]
    assert moe["router"].shape == (32, 16) and moe["router_bias"].shape == (
        16,)
    assert moe["experts_gate_up"].shape == (4, 32, 32)
    assert moe["experts_down"].shape == (4, 16, 32)
    pairs = adapters["layer_3"]["moe"]
    assert pairs["lora_experts_gate_a"].shape == (4, 32, 4)
    assert pairs["lora_experts_down_b"].shape == (4, 4, 32)
    assert set(adapters["layer_3"]["attn"]) == {
        f"lora_{p}_proj_{h}" for p in "qkvo" for h in "ab"}
    assert set(adapters["layer_0"]["mlp"]) == set(pairs["shared"]) == {
        f"lora_{p}_proj_{h}" for p in ("gate", "up", "down") for h in "ab"}
    # neither the router, the norms, the embedding nor the head has a pair
    assert set(adapters) == {f"layer_{i}" for i in range(5)}
    assert float(jnp.abs(pairs["lora_experts_up_b"]).max()) > 0


@pytest.mark.parametrize("attention,token_block", [
    ("dense", None), ("flash", None), ("flash", 8)])
def test_float32_logits_loss_and_every_adapter_gradient(
        seeded, reference, attention, token_block):
    """The model in float32 (window layers, the full layer, the dense layer
    and four expert layers) against the dense, every-expert-over-every-token
    reference, whole and in blocks of tokens as the chip runs it: 1e-5."""
    _, base, adapters, ids, labels = seeded
    model = k_exaone(**CFG, base_dtype="float32", attention=attention)
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
    from flax.traverse_util import flatten_dict

    got, wanted = flatten_dict(grads), flatten_dict(want_grads)
    assert set(got) == set(wanted)
    for path, w in wanted.items():
        assert float(jnp.abs(w).max()) > 0, path    # every pair is bound
        np.testing.assert_allclose(
            got[path], w, atol=1e-5 * float(jnp.abs(w).max()),
            err_msg="/".join(path))


def test_the_shares_of_an_expert_parallel_layer_add_up(seeded, reference):
    """One expert layer cut into 4 shares of 4 experts, each computed by the
    program as its own shard (``first_expert_held`` 0, 4, 8, 12), the shared
    expert counted once: the uncut 16-expert layer of the reference."""
    cfg16 = {**CFG, "num_experts_held": 16, "first_expert_held": 0}
    whole = reference.init_base({**cfg16, "base_dtype": "float32"}, 34)
    moe = _spread_router({"l": {"moe": whole["layer_1"]["moe"]}})["l"]["moe"]
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(1, T, 32)), jnp.float32)
    pairs = {f"lora_experts_{n}_{h}": jnp.asarray(rng.normal(
        size=(16, i, 4) if h == "a" else (16, 4, o)) * 0.1, jnp.float32)
        for n, (i, o) in {"gate": (32, 16), "up": (32, 16),
                          "down": (16, 32)}.items() for h in "ab"}
    shared = {f"lora_{n}_proj_{h}": jnp.asarray(rng.normal(
        size=(i, 4) if h == "a" else (4, o)) * 0.1, jnp.float32)
        for n, (i, o) in {"gate": (32, 16), "up": (32, 16),
                          "down": (16, 32)}.items() for h in "ab"}
    with jax.default_matmul_precision("highest"):
        want = reference.sparse_moe(moe, {**pairs, "shared": shared}, x[0],
                                    cfg16)
        only_shared = reference.gated_mlp(moe["shared"], shared, x[0], cfg16)
        total, tokens = 0.0, 0.0
        for first in range(0, 16, 4):
            shapes = k_exaone(**{**CFG, "first_expert_held": first},
                              base_dtype="float32").cfg
            cut = lambda a: a[first:first + 4]      # noqa: E731
            params = {**moe, "experts_gate_up": cut(moe["experts_gate_up"]),
                      "experts_down": cut(moe["experts_down"]),
                      **{k: cut(v) for k, v in pairs.items()},
                      "shared": {**moe["shared"], **shared}}
            share, state = SparseMoE(shapes, jnp.float32).apply(
                {"params": params}, x, mutable=["counters"])
            total = total + share[0] - only_shared
            tokens += float(jnp.sum(state["counters"]["expert_tokens"]))
    np.testing.assert_allclose(total + only_shared, want, atol=1e-5 * float(
        jnp.abs(want).max()))
    assert tokens == T * 3      # every assignment lies in exactly one share


def test_a_selection_bias_selects_and_does_not_weigh():
    """``route_sigmoid``: the bias changes WHICH experts some tokens are
    sent to; a chosen expert's weight is ``scale * s / sum s`` of the
    unbiased scores whatever the bias."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(32, 16)) * 0.3, jnp.float32)
    bias = jnp.asarray(rng.normal(size=(16,)) * 0.3, jnp.float32)
    scores = jax.nn.sigmoid(jnp.dot(x, w, precision="highest"))
    plain = ep.route_sigmoid(x, w, jnp.zeros(16), 3, 2.5)
    biased = ep.route_sigmoid(x, w, bias, 3, 2.5)
    assert not np.array_equal(np.sort(plain[0], -1), np.sort(biased[0], -1))
    for idx, weight in (plain, biased):
        chosen = jnp.take_along_axis(scores, idx, axis=-1)
        np.testing.assert_allclose(
            weight, 2.5 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-6)
        np.testing.assert_allclose(weight.sum(-1), 2.5, rtol=1e-6)
    # the biased choice is the top-3 of score + bias
    np.testing.assert_array_equal(
        np.sort(biased[0], -1),
        np.sort(np.argsort(-(np.asarray(scores) + np.asarray(bias)),
                           axis=-1)[:, :3], -1))
    unscaled = ep.route_sigmoid(x, w, bias, 3, 1.0, renormalise=False)
    np.testing.assert_allclose(
        unscaled[1], jnp.take_along_axis(scores, unscaled[0], -1), rtol=1e-6)


# --- the held experts' product ------------------------------------------------

H, D, F, R, K, FIRST = 4, 16, 8, 2, 3, 2


def _held_operands(seed=0, clients=3, n=48, d=D, f=F):
    rng = np.random.default_rng(seed)
    mk = lambda *s: jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)  # noqa: E731
    pairs = ep.ExpertPairs(mk(clients, H, d, R), mk(clients, H, R, f),
                           mk(clients, H, d, R), mk(clients, H, R, f),
                           mk(clients, H, f, R), mk(clients, H, R, d))
    return (mk(clients, n, d), mk(H, d, 2 * f), mk(H, f, d), pairs,
            jnp.asarray(rng.integers(0, 12, (clients, n, K))), jnp.abs(
                mk(clients, n, K)))


def _plain(x, idx, weight, w_gate_up, w_down, pairs, scale):
    """Every held expert over every token, masked by the routing."""
    out, f = 0.0, w_down.shape[1]
    for e in range(H):
        w_e = jnp.sum(jnp.where(idx == FIRST + e, weight, 0.0), -1)
        gate = x @ w_gate_up[e][:, :f] + scale * (
            x @ pairs.gate_a[e]) @ pairs.gate_b[e]
        up = x @ w_gate_up[e][:, f:] + scale * (
            x @ pairs.up_a[e]) @ pairs.up_b[e]
        hidden = jax.nn.silu(gate) * up
        out = out + w_e[:, None] * (hidden @ w_down[e] + scale * (
            hidden @ pairs.down_a[e]) @ pairs.down_b[e])
    return out


def _product(x, idx, weight, w_gate_up, w_down, pairs, rows):
    held = ep.sort_held(idx, H, FIRST)
    y, computed, _ = ep.held_lora_products(x, weight, held, w_gate_up,
                                           w_down, pairs, 2.0, rows)
    return y, (computed, held.counts)


@pytest.mark.parametrize("rows", [16, 40, 96])
def test_the_held_product_under_vmap_is_the_loop_over_clients(rows):
    """Output and every gradient (``x``, the routing weights, the six pairs)
    of the batched call equal each client's own, which equal the plain
    every-expert-over-every-token sum; at chunks of 16 rows a client takes
    three or more, at 96 (``chunk_rows``' own) none fills its one, and every
    assignment is still computed."""
    x, w_gate_up, w_down, pairs, idx, weight = _held_operands()

    def loss(x, weight, pairs, idx, fn):
        return jnp.sum(fn(x, weight, pairs, idx) ** 2)

    ours = lambda x, w, p, i: _product(  # noqa: E731
        x, i, w, w_gate_up, w_down, p, rows)[0]
    plain = lambda x, w, p, i: _plain(  # noqa: E731
        x, i, w, w_gate_up, w_down, p, 2.0)
    with jax.default_matmul_precision("highest"):
        batched, (computed, counts) = jax.vmap(
            lambda x, i, w, p: _product(x, i, w, w_gate_up, w_down, p, rows))(
                x, idx, weight, pairs)
        grads = jax.vmap(jax.grad(loss, (0, 1, 2)), in_axes=(
            0, 0, 0, 0, None))(x, weight, pairs, idx, ours)
        for c in range(x.shape[0]):
            one = jax.tree.map(lambda a: a[c], (x, weight, pairs, idx))
            want = plain(*one)
            np.testing.assert_allclose(batched[c], want, atol=1e-5 * float(
                jnp.abs(want).max()))
            np.testing.assert_allclose(batched[c], ours(*one), atol=1e-6)
            want_grads = jax.grad(loss, (0, 1, 2))(*one, plain)
            assert _relative(jax.tree.map(lambda a: a[c], grads),
                             want_grads) < 1e-5
    np.testing.assert_array_equal(computed, counts.sum(-1))
    if rows == 16:
        assert int(counts.sum(-1).max()) > 2 * 16   # three chunks somewhere
    assert int(counts.sum(-1).max()) < 96 == ep.chunk_rows(48, K, 12, H)


def test_a_held_expert_at_three_times_the_mean_load_is_computed_whole():
    """All of a client's tokens choose held expert 1 (far over three times
    the mean load, and with the others' more than two chunks hold): no
    assignment is left out, whatever ``CHUNK_FACTOR`` says."""
    x, w_gate_up, w_down, pairs, idx, weight = _held_operands(seed=3)
    x, pairs, idx, weight = jax.tree.map(lambda a: a[0],
                                         (x, pairs, idx, weight))
    n = x.shape[0]
    idx = idx.at[:, 0].set(FIRST + 1).at[:, 1:].set(
        jnp.where(idx[:, 1:] == FIRST + 1, 11, idx[:, 1:]))
    # the chunk a model of 48 experts would ask for: CHUNK_FACTOR times the
    # mean number of held assignments
    rows = ep.chunk_rows(n, K, 48, H)
    y, (computed, counts) = _product(x, idx, weight, w_gate_up, w_down, pairs,
                                     rows)
    assert int(counts[1]) == n >= 2 * rows < int(counts.sum())
    assert int(counts[1]) >= 3 * n * K / 48
    assert int(computed) == int(counts.sum())
    with jax.default_matmul_precision("highest"):
        want = _plain(x, idx, weight, w_gate_up, w_down, pairs, 2.0)
        y, _ = _product(x, idx, weight, w_gate_up, w_down, pairs, rows)
    np.testing.assert_allclose(y, want, atol=1e-5 * float(jnp.abs(want).max()))


def _eqns_with(jaxpr, shapes, name, results=False):
    """``[shapes]`` of the operands (of the results, with ``results``) of
    every equation of primitive ``name`` (nested jaxprs too) one of which
    has a shape in ``shapes``."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            values = [tuple(v.aval.shape)
                      for v in (eqn.outvars if results else eqn.invars)]
            if any(s in shapes for s in values):
                found.append(values)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(_eqns_with(sub, shapes, name, results))
    return found


@pytest.mark.parametrize("d,f,rows,user", [
    (D, F, 24, "dot_general"), (128, 256, 128, "pallas_call")],
    ids=["plain", "kernel"])
def test_the_batched_call_reads_the_frozen_matrices_in_place(d, f, rows, user):
    """Under ``vmap`` over clients, forward and backward: every grouped
    product with an expert matrix (the plain masked ``dot_general`` at toy
    widths, the kernel at whole lanes) takes it as it lies, ``[H, d, 2f]`` /
    ``[H, f, d]`` with no client axis, and nothing gathers, slices or copies
    it (its only uses are those products); no product's result has a
    matrix's shape, so none forms its gradient."""
    x, w_gate_up, w_down, pairs, idx, weight = _held_operands(d=d, f=f)

    def step(x, idx, weight, pairs, w_gate_up, w_down):
        # no activation has an expert matrix's shape at these rows a chunk
        return jax.grad(lambda x, p: jnp.sum(_product(
            x, idx, weight, w_gate_up, w_down, p, rows)[0] ** 2), (0, 1))(
                x, pairs)

    jaxpr = jax.make_jaxpr(jax.vmap(
        step, in_axes=(0, 0, 0, 0, None, None)))(
            x, idx, weight, pairs, w_gate_up, w_down)
    frozen = {tuple(w_gate_up.shape), tuple(w_down.shape)}
    # first chunk forward and backward, and the further chunks' loops
    assert len(_eqns_with(jaxpr.jaxpr, frozen, user)) >= 8
    assert not _eqns_with(jaxpr.jaxpr, frozen, "dot_general", results=True)
    batched = {(x.shape[0],) + s for s in frozen}
    text = str(jaxpr)
    for s in batched:
        assert "f32[" + ",".join(map(str, s)) + "]" not in text
    # the matrices are used by products alone (and handed on into the
    # custom backward, its loops and the batched kernel's loop over clients,
    # a ``scan`` that takes them whole): nothing gathers, slices or copies them
    assert _users_of(jaxpr.jaxpr, frozen) <= {
        user, "while", "scan", "custom_vjp_call", "custom_vjp_call_jaxpr",
        "pjit", "jit", "closed_call", "convert_element_type"}


def _users_of(jaxpr, shapes) -> set:
    """Names of the primitives (nested jaxprs too) that read a value whose
    shape is in ``shapes``."""
    names = set()
    for eqn in jaxpr.eqns:
        if any(tuple(getattr(v.aval, "shape", ())) in shapes
               for v in eqn.invars):
            names.add(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names |= _users_of(sub, shapes)
    return names


# --- the federated adapter round ----------------------------------------------

CLIENTS, PER_CLIENT, BATCH = 4, 2, 2


@pytest.fixture(scope="module")
def federation():
    rng = np.random.default_rng(5)
    x = rng.integers(1, 97, (CLIENTS * PER_CLIENT, T)).astype(np.int32)
    y = np.concatenate([x[:, 1:], np.zeros((len(x), 1), np.int32)], axis=1)
    parts = {c: np.arange(c * PER_CLIENT, (c + 1) * PER_CLIENT)
             for c in range(CLIENTS)}
    return x, y, parts


def _api(federation, reference, model_cfg=CFG, base_dtype="float32",
         **cfg_more):
    x, y, parts = federation
    fed = build_federated_arrays(x, y, parts, BATCH)
    cfg = FedConfig(client_num_in_total=CLIENTS, client_num_per_round=CLIENTS,
                    comm_round=4, epochs=2, batch_size=BATCH,
                    client_optimizer="sgd", lr=0.5, seed=7, **cfg_more)
    weights = _spread_router(reference.init_base(
        {**model_cfg, "base_dtype": base_dtype}, 3400000555))
    return FedAdapterAPI(
        k_exaone(**model_cfg, base_dtype=base_dtype, attention="flash"), fed,
        None, cfg, loss_fn=token_ce, base_params=weights), weights


def test_one_round_of_four_clients_is_the_references_round(federation,
                                                           reference):
    """``FedAdapterAPI.train_one_round`` (4 clients under one vmap, 2 local
    steps each, float32) against ``reference.fedavg_round`` from the same
    seeded base and adapters; the base is the very arrays handed in, bit for
    bit what it was; the round carries the model's counters."""
    x, y, parts = federation
    with jax.default_matmul_precision("highest"):
        api, weights = _api(federation, reference)
        before = jax.tree.map(np.asarray, weights)
        start = jax.tree.map(jnp.asarray, jax.tree.map(
            np.asarray, api.net.params))
        counters0 = jax.tree.map(np.asarray, api.net.model_state["counters"])
        loss = api.train_one_round(0)["train_loss"]
        clients = [([(x[parts[c]], y[parts[c]])], PER_CLIENT)
                   for c in range(CLIENTS)]
        want, want_loss = reference.fedavg_round(
            start, clients, dict(CFG, base=api.base), lr=0.5, epochs=2)
    assert loss == pytest.approx(want_loss, abs=1e-5)
    update = jax.tree.map(lambda a, b: a - b, api.net.params, start)
    wanted = jax.tree.map(lambda a, b: a - b, want, start)
    assert _relative(update, wanted) < 1e-4
    for a, b, c in zip(jax.tree.leaves(before), jax.tree.leaves(api.base),
                       jax.tree.leaves(weights)):
        assert b is c
        np.testing.assert_array_equal(a, np.asarray(b))
    # zero at init; after a round the cohort's mean of what a client's two
    # steps counted: BATCH * T tokens x top-3 over 16 experts, a layer
    assert all(float(np.sum(v)) == 0 for v in jax.tree.leaves(counters0))
    counters = jax.tree.map(np.asarray, api.net.model_state["counters"])
    assert set(counters) == {f"layer_{i}" for i in range(1, 5)}
    for layer in counters.values():
        held = layer["moe"]
        assert held["expert_tokens"].shape == (4,)
        assert 0 < float(held["expert_tokens"].sum()) <= 2 * BATCH * T * 3
        assert float(held["uncomputed_tokens"]) == 0
        assert float(held["further_passes"]) >= 0
        assert 0 <= float(held["unrouted_tokens"]) <= 2 * BATCH * T
    api.train_one_round(1)
    again = api.net.model_state["counters"]["layer_1"]["moe"]["expert_tokens"]
    assert float(jnp.sum(again)) > float(
        counters["layer_1"]["moe"]["expert_tokens"].sum())


def test_the_round_holds_the_base_as_operands_and_no_copy_of_an_expert(
        federation, reference):
    """The lowered fused round (bf16 step, bf16 base) takes every base
    tensor as an argument, holds no literal of a matrix's size, and no
    tensor with a client axis before an expert matrix's shape: the vmap over
    clients leaves the frozen experts one operand, and no gather makes a
    copy of them a slab. ``adapter_profile`` counts the experts held and
    the pairs (those the held experts' product computes itself too)."""
    api, _ = _api(federation, reference, base_dtype="bfloat16",
                  client_step_dtype="bf16")
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
    for matrix in ("4x32x32", "4x16x32"):       # [held, d, 2f], [held, f, d]
        assert f"tensor<{matrix}xbf16>" in text
        assert not re.search(rf"tensor<\d+x{matrix}x(bf16|f32)>", text)
    profile = api.adapter_profile()
    assert profile["experts_held"] == 4 * 4
    assert profile["base_bytes_operand"] == 2 * profile["base_params"]
    api.train_one_round(0)
    after = api.adapter_profile()
    # 5 attention layers x 4 + the dense MLP's 3 + 4 shared experts x 3 go
    # through ops.lora_linear, 4 layers x 4 held experts x 3 are noted by the
    # held product; at these widths none takes the kernel
    assert (after["lora_sites"], after["lora_sites_fused"]) == (
        20 + 3 + 12 + 48, 0)
    assert after["adapter_bytes_folded"] == CLIENTS * 4 * after[
        "adapter_params"]


def test_a_model_with_batch_statistics_is_still_refused():
    """The adapter round carries a model's other collections; BatchNorm's
    running statistics belong to the frozen layers and are refused."""
    import flax.linen as nn

    from fedml_tpu.models.adapter import adapter_model_fns

    class Normed(nn.Module):
        @nn.compact
        def __call__(self, x, train: bool = False):
            a = self.param("lora_p_a", nn.initializers.ones, (4, 2))
            b = self.param("lora_p_b", nn.initializers.ones, (2, 4))
            x = nn.Dense(4)(x) + x @ a @ b
            return nn.BatchNorm(use_running_average=not train)(x)

    with pytest.raises(NotImplementedError, match="BatchNorm"):
        adapter_model_fns(Normed()).init(jax.random.PRNGKey(0),
                                         jnp.ones((2, 4)))


def test_the_factory_takes_the_sources_keys_and_refuses_what_it_cannot_run():
    shapes = k_exaone(**CFG).cfg
    assert shapes.rope_theta == 1e6 and shapes.num_experts_held == 4
    assert len(KExaoneShapes().layer_types) == 48
    assert KExaoneShapes().mlp_layer_types[:2] == ("dense", "sparse")
    assert k_exaone(**{k: v for k, v in CFG.items() if k not in (
        "num_experts_held", "first_expert_held")}).cfg.num_experts_held == 16
    with pytest.raises(NotImplementedError, match="scoring_func"):
        k_exaone(**{**CFG, "scoring_func": "softmax"})
    with pytest.raises(NotImplementedError, match="n_group"):
        k_exaone(**{**CFG, "n_group": 8})
    with pytest.raises(NotImplementedError, match="rotary"):
        k_exaone(**{**CFG, "rope_parameters": {"rope_theta": 1e6,
                                               "rope_type": "yarn"}})
    with pytest.raises(TypeError, match="unknown keys"):
        k_exaone(**CFG, sliding_typo=1)
    with pytest.raises(ValueError, match="layer_types"):
        k_exaone(**{**CFG, "num_hidden_layers": 60})
    with pytest.raises(ValueError, match="held experts"):
        k_exaone(**{**CFG, "first_expert_held": 14})
    from fedml_tpu.models.registry import create_model

    assert create_model("k_exaone", **CFG).cfg.hidden_size == 32


def test_balancing_spreads_a_drawn_routers_tokens(reference):
    """``balance_router``: the selection biases by the router's own
    balancing rule on given tokens. The fullest of ALL experts then draws
    about the mean; only ``router_bias`` changes; the same seed and tokens
    give the same biases."""
    cfg = {**CFG, "num_experts_held": 16, "first_expert_held": 0,
           "base_dtype": "float32"}
    base = _spread_router(reference.init_base(cfg, 34))
    ids = np.random.default_rng(2).integers(2, 97, (4, 64))
    balanced, found = reference.balance_router(base, cfg, ids)
    again, _ = reference.balance_router(base, cfg, ids)
    assert len(found) == 4      # the sparse layers
    for before, after in found:
        assert after <= before and after < 1.15, found
    assert max(before for before, _ in found) > 1.3
    from flax.traverse_util import flatten_dict

    flat, flat0, flat1 = (flatten_dict(t) for t in (balanced, base, again))
    for path, leaf in flat.items():
        np.testing.assert_array_equal(leaf, flat1[path])
        if path[-1] == "router_bias":
            assert not np.array_equal(leaf, flat0[path])
            assert leaf.dtype == flat0[path].dtype
        else:
            assert leaf is flat0[path]


def test_the_drawn_base_follows_the_assumed_laws(reference):
    """``init_base``: structure, shapes and dtype of the model's own frozen
    tree; the embedding at unit scale, the branch norms at 0.05, the head at
    0.002, every other matrix at 0.02; the model's own fresh init draws by
    the same laws."""
    kwargs = {**CFG, "base_dtype": "bfloat16"}
    weights = reference.init_base(kwargs, 3400000555)
    model = k_exaone(**kwargs)
    ids = jnp.ones((1, 8), jnp.int32)
    fresh = split_frozen(jax.jit(lambda i: model.init(
        {"params": jax.random.PRNGKey(0)}, i))(ids)["params"])[0]
    assert jax.tree.structure(weights) == jax.tree.structure(fresh)
    f32 = lambda a: np.asarray(a.astype(jnp.float32))  # noqa: E731
    for a, b in zip(jax.tree.leaves(weights), jax.tree.leaves(fresh)):
        assert a.shape == b.shape and a.dtype == b.dtype == jnp.bfloat16
    for tree in (weights, fresh):
        assert abs(float(np.std(f32(tree["embed"]))) - 1.0) < 0.05
        assert abs(float(np.std(f32(tree["lm_head"]))) - 0.002) < 2e-4
        layer = tree["layer_3"]
        assert np.allclose(f32(layer["post_attn_norm"]), 0.05, atol=1e-3)
        assert np.allclose(f32(layer["post_ffn_norm"]), 0.05, atol=1e-3)
        assert np.all(f32(layer["attn"]["q_norm"]) == 1) and np.all(
            f32(tree["final_norm"]) == 1)
        assert abs(float(np.std(f32(layer["moe"]["experts_gate_up"])))
                   - 0.02) < 2e-3
        assert abs(float(np.std(f32(layer["moe"]["router_bias"])))
                   - 0.05) < 0.03
    other = reference.init_base(kwargs, 3400000556)
    assert not np.array_equal(f32(weights["embed"]), f32(other["embed"]))
    np.testing.assert_array_equal(
        f32(weights["embed"]),
        f32(reference.init_base(kwargs, 3400000555)["embed"]))


# --- which attention program each layer kind lowers to (PR 37) ----------------

def _attention_text(window: int, attention: str) -> str:
    """One attention layer's lowered text at the tests' widths (4 query heads
    over 2 key-value heads of 8, 2 sequences of 24 tokens): the forward and
    the gradient of the adapters and the input."""
    from fedml_tpu.models.k_exaone import Attention

    cfg = k_exaone(**CFG, base_dtype="float32", attention=attention).cfg
    layer = Attention(cfg, jnp.float32, window)
    x = jnp.zeros((2, T, cfg.hidden_size))
    params = jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)
    return jax.jit(jax.grad(lambda p, x: jnp.sum(layer.apply(p, x)),
                            (0, 1))).lower(params, x).as_text()


def test_the_window_layer_lowers_to_the_band_kernels_on_unrepeated_heads():
    """The window layer's flash path: k and v are never broadcast to the 4
    query heads, nothing is transposed to ``[B*H, T, D]`` = ``[8, 24, 8]``,
    and the three band calls take q ``[2, 24, 32]`` and k, v ``[2, 24, 16]``
    (``dk``, ``dv`` leave with their 2 heads); the full-attention layer's
    text, beside it, holds all three."""
    text, full = _attention_text(6, "flash"), _attention_text(0, "flash")
    repeat = r"broadcast_in_dim.*tensor<2x24x2x2x8xf32>"
    heads_first = r"tensor<8x24x8xf32>|tensor<2x4x24x8xf32>"
    assert re.search(repeat, full) and re.search(heads_first, full)
    assert not re.search(repeat, text) and not re.search(heads_first, text)
    for name, results in (
            ("_band_fwd", r"tensor<2x24x32xf32>, tensor<2x2x2x24xf32>"),
            ("_band_dq", r"tensor<2x24x32xf32>"),
            ("_band_dkv", r"tensor<2x24x16xf32>, tensor<2x24x16xf32>")):
        assert re.search(rf"call @{name}\w*\(.*-> \(?{results}\)?", text), name
        assert name not in full


def test_a_step_calls_the_band_twice_forward_and_once_backward_a_layer(
        seeded):
    """The model's four window layers under ``nn.remat``: a training step
    calls ``window_band_fwd`` twice a layer (the forward and the recomputed
    one) and ``window_band_dq`` / ``window_band_dkv`` once; the full layer
    keeps the streaming kernels (the benchmark's cell holds the same 4 layers
    at 8 sequence-steps a round: 32 x (2 + 1) calls)."""
    model, base, adapters, ids, labels = seeded
    text = jax.jit(lambda a: _loss_and_grad(
        model, base, a, ids, labels)).lower(adapters).as_text()
    calls = re.findall(r"call @(_band_[a-z]+)", text)
    assert {n: calls.count(n) for n in set(calls)} == {
        "_band_fwd": 8, "_band_dq": 4, "_band_dkv": 4}
    jaxpr = str(jax.make_jaxpr(lambda a: _loss_and_grad(
        model, base, a, ids, labels))(adapters))
    assert set(re.findall(r"name=(window_band_\w+)", jaxpr)) == {
        "window_band_fwd", "window_band_dq", "window_band_dkv"}


@pytest.mark.parametrize("window,attention", [
    (0, "flash"), (0, "dense"), (6, "dense")])
def test_the_other_attention_arms_lower_to_the_parents_text(window, attention):
    """The full-attention layer (the streaming kernel over repeated k, v) and
    the einsum arm of both kinds: ``tests/fixtures/k_exaone_attention_text
    .json`` holds the SHA-256 of this text as PR 37's parent (5c1f4eb)
    lowered it, the same function run in a copy of that commit."""
    import hashlib
    import json

    with open(os.path.join(ROOT, "tests", "fixtures",
                           "k_exaone_attention_text.json")) as f:
        want = json.load(f)["sha256"][f"window_{window}_{attention}"]
    text = _attention_text(window, attention)
    assert hashlib.sha256(text.encode()).hexdigest() == want
    assert "_band_" not in text
