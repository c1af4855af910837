"""Nemotron-H (``models/nemotron_h.py``) against its plain reference
(``benchmark/reference_nemotron_h.py``) on seeded weights: each kind of
single-mixer block alone and the nine together, the shares of an
expert-parallel layer summed, the held experts' product in its ``relu2`` form
against a loop over the experts (and its SwiGLU form pinned to what it lowered
to before the form was a parameter), the grouped gated norm, the factory and
the drawn base. CPU, small sizes."""

import hashlib
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.traverse_util import flatten_dict

from fedml_tpu.models.adapter import merge_params, split_frozen
from fedml_tpu.models.granite_hybrid import group_rms_norm, rms_norm
from fedml_tpu.models.nemotron_h import (NemotronHShapes, SparseMoE,
                                         nemotron_h, token_ce)
from fedml_tpu.parallel import expert_parallel as ep

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the source's keys at the CPU tests' sizes: a stream of 48, Mamba-2 with 8
#: heads of 8 in 4 B/C groups of state 8 (chunks of 8), 4 query heads over 2
#: key-value heads of 16 (4 x 16 = 64 is NOT the stream's 48), 16 experts
#: top-3 of width 20 (off every grid) of which 8 (experts 8-15) are held, a
#: shared expert of width 40
SMALL = dict(
    vocab_size=97, hidden_size=48, num_hidden_layers=9,
    hybrid_override_pattern="MEMEM*EME", mamba_num_heads=8, mamba_head_dim=8,
    ssm_state_size=8, n_groups=4, conv_kernel=4, chunk_size=8,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    n_routed_experts=16, num_experts_per_tok=3, moe_intermediate_size=20,
    moe_shared_expert_intermediate_size=40, num_experts_held=8,
    first_expert_held=8, adapter_rank=4, adapter_alpha=8.0,
    adapter_b_std=0.01)
#: the rest of the source's dictionary, as published
PUBLISHED = dict(
    attention_bias=False, expand=2, intermediate_size=1856,
    layer_norm_epsilon=1e-05, mamba_hidden_act="silu", mamba_proj_bias=False,
    max_position_embeddings=262144, mlp_bias=False, mlp_hidden_act="relu2",
    model_type="nemotron_h", n_group=1, n_shared_experts=1, norm_eps=1e-05,
    norm_topk_prob=True, num_logits_to_keep=1, partial_rotary_factor=1,
    rescale_prenorm_residual=True, residual_in_fp32=False, rope_theta=10000,
    routed_scaling_factor=2.5, sliding_window=None, tie_word_embeddings=False,
    time_step_floor=0.0001, time_step_max=0.1, time_step_min=0.001,
    topk_group=1, use_bias=False, use_conv_bias=True, use_mamba_kernels=True)
CFG = {**PUBLISHED, **SMALL}
T = 24


def _reference():
    spec = importlib.util.spec_from_file_location(
        "test_reference_nemotron_h",
        os.path.join(ROOT, "benchmark", "reference_nemotron_h.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def reference():
    return _reference()


def _spread_router(base, factor=12.0):
    """The toy width's router scores all lie near 1/2, so the bias alone
    would select: scale the router up so that the scores spread as the
    published width's do."""
    out = jax.tree.map(lambda a: a, base)
    for layer in out.values():
        if isinstance(layer, dict) and "moe" in layer:
            layer["moe"] = dict(layer["moe"],
                                router=layer["moe"]["router"] * factor)
    return out


def _seeded(pattern, attention="dense"):
    """``(model, cfg, base, adapters, ids, labels)`` on seeded float32
    weights for the blocks ``pattern`` names."""
    cfg = dict(CFG, hybrid_override_pattern=pattern,
               num_hidden_layers=len(pattern))
    model = nemotron_h(**cfg, base_dtype="float32", attention=attention)
    ids = jax.random.randint(jax.random.PRNGKey(0), (2, T), 1, 97)
    labels = jnp.concatenate(
        [ids[:, 1:], jnp.zeros((2, 1), jnp.int32)], axis=1)
    params = jax.jit(lambda r, x: model.init({"params": r}, x))(
        jax.random.PRNGKey(1), ids)["params"]
    base, adapters = split_frozen(params)
    return model, cfg, _spread_router(base), adapters, ids, labels


def _relative(got, want):
    num = sum(float(jnp.sum((g - w) ** 2)) for g, w in zip(
        jax.tree.leaves(got), jax.tree.leaves(want)))
    den = sum(float(jnp.sum(w ** 2)) for w in jax.tree.leaves(want))
    return (num / den) ** 0.5


# --- the model against the reference ----------------------------------------

def test_the_tree_holds_what_the_configuration_says():
    model = nemotron_h(**CFG, base_dtype="float32")
    base, adapters = split_frozen(jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32)))["params"])
    kinds = NemotronHShapes(**SMALL).kinds
    assert kinds == ("mamba", "moe", "mamba", "moe", "mamba", "attn", "moe",
                     "mamba", "moe")
    for i, kind in enumerate(kinds):    # ONE mixer a block, and its norm
        assert set(base[f"layer_{i}"]) == {"norm", kind}
    mamba = base["layer_0"]["mamba"]
    # [z | x B C | dt] = 64 + (64 + 2 * 4 * 8) + 8
    assert mamba["in_proj"].shape == (48, 64 + 128 + 8)
    assert mamba["conv_weight"].shape == (4, 128)
    assert mamba["norm_weight"].shape == (64,)
    attn = base["layer_5"]["attn"]
    assert attn["q_proj"].shape == (48, 64) and attn["k_proj"].shape == (
        48, 32) and attn["o_proj"].shape == (64, 48)
    moe = base["layer_1"]["moe"]
    assert moe["router"].shape == (48, 16)
    # a hidden unit a row in ``experts_up``: the stream's width last
    assert moe["experts_up"].shape == (8, 20, 48)
    assert moe["experts_down"].shape == (8, 20, 48)
    assert set(moe["shared"]) == {"up_proj", "down_proj"}
    pairs = adapters["layer_1"]["moe"]
    assert set(pairs) == {"lora_experts_up_a", "lora_experts_up_b",
                          "lora_experts_down_a", "lora_experts_down_b",
                          "shared"}
    assert pairs["lora_experts_up_a"].shape == (8, 48, 4)
    assert pairs["lora_experts_down_b"].shape == (8, 4, 48)
    assert set(adapters["layer_0"]["mamba"]) == {
        f"lora_{n}_proj_{h}" for n in ("in", "out") for h in "ab"}
    # not the router, the norms, the convolution, the embedding or the head
    assert not [p for p in flatten_dict(adapters) if not p[-1].startswith(
        "lora_")]


@pytest.mark.parametrize("pattern,attention,token_block", [
    ("M", "dense", None), ("*", "dense", None), ("*", "flash", None),
    ("E", "dense", None), ("MEMEM*EME", "flash", 8)],
    ids=["mamba", "attn_dense", "attn_flash", "experts",
         "nine_flash_in_blocks"])
def test_float32_logits_loss_and_every_adapter_gradient(
        reference, pattern, attention, token_block):
    """The model in float32, each kind of block alone and the nine together,
    against the token-by-token, dense, every-expert-over-every-token
    reference, whole and in blocks of tokens as the chip runs it: 1e-5."""
    model, cfg, base, adapters, ids, labels = _seeded(pattern, attention)
    if token_block:
        cfg = dict(cfg, token_block=token_block)

    def loss_of(a):
        logits = model.apply({"params": merge_params(base, a)}, ids)
        return jnp.mean(token_ce(logits, labels)), logits

    with jax.default_matmul_precision("highest"):
        (loss, logits), grads = jax.jit(jax.value_and_grad(
            loss_of, has_aux=True))(adapters)
        want = jnp.stack(jax.jit(lambda b, a: [reference.logits(
            b, a, ids[i], cfg) for i in range(2)])(base, adapters))
        want_loss, want_grads = reference.loss_and_grad(
            dict(cfg, base=base))(adapters, ids, labels)
    np.testing.assert_allclose(logits, want, atol=1e-5 * float(
        jnp.abs(want).max()))
    assert float(loss) == pytest.approx(float(want_loss), abs=1e-5)
    got, wanted = flatten_dict(grads), flatten_dict(want_grads)
    assert set(got) == set(wanted)
    for path, w in wanted.items():
        assert float(jnp.abs(w).max()) > 0, path    # every pair is bound
        np.testing.assert_allclose(
            got[path], w, atol=1e-5 * float(jnp.abs(w).max()),
            err_msg="/".join(path))


def test_the_shares_of_an_expert_parallel_layer_add_up(reference):
    """One expert block cut into 2 shares of 8 experts, each computed by the
    program as its own shard (``first_expert_held`` 0 and 8, the 2-way layer
    of the benchmark's deployment at the tests' size), the shared expert
    counted once: the shares sum to the uncut reference's layer."""
    _, cfg, base, adapters, _, _ = _seeded("E")
    moe = base["layer_0"]["moe"]
    rng = np.random.default_rng(0)
    mk = lambda *s: jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)  # noqa: E731
    whole = dict(moe, experts_up=mk(16, 20, 48) * 0.3,
                 experts_down=mk(16, 20, 48) * 0.3)
    pairs = {"lora_experts_up_a": mk(16, 48, 4),
             "lora_experts_up_b": mk(16, 4, 20),
             "lora_experts_down_a": mk(16, 20, 4),
             "lora_experts_down_b": mk(16, 4, 48)}
    shared = adapters["layer_0"]["moe"]["shared"]
    x = mk(1, T, 48)
    uncut = dict(cfg, num_experts_held=16, first_expert_held=0)
    with jax.default_matmul_precision("highest"):
        want = reference.sparse_moe(whole, {**pairs, "shared": shared}, x[0],
                                    uncut)
        only_shared = reference.relu2_mlp(whole["shared"], shared, x[0],
                                          uncut)
        total, tokens = 0.0, 0.0
        for first in (0, 8):
            shapes = nemotron_h(**{**cfg, "first_expert_held": first},
                                base_dtype="float32").cfg
            cut = lambda a: a[first:first + 8]  # noqa: E731
            share = {**{k: cut(v) for k, v in pairs.items()},
                     "router": whole["router"],
                     "router_bias": whole["router_bias"],
                     "experts_up": cut(whole["experts_up"]),
                     "experts_down": cut(whole["experts_down"]),
                     "shared": {**whole["shared"], **shared}}
            out, state = SparseMoE(shapes, jnp.float32).apply(
                {"params": share}, x, mutable=["counters"])
            total = total + out[0] - only_shared
            tokens += float(state["counters"]["expert_tokens"].sum())
    np.testing.assert_allclose(total + only_shared, want, atol=1e-5 * float(
        jnp.abs(want).max()))
    assert tokens == T * 3      # every assignment lies in exactly one share


# --- the held experts' product, its two forms ---------------------------------

H, D, F, R, K, FIRST = 4, 16, 10, 2, 3, 2


def _relu2_operands(seed=0, clients=3, n=48):
    rng = np.random.default_rng(seed)
    mk = lambda *s: jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)  # noqa: E731
    pairs = ep.ExpertPairs(None, None, mk(clients, H, D, R),
                           mk(clients, H, R, F), mk(clients, H, F, R),
                           mk(clients, H, R, D))
    return (mk(clients, n, D), mk(H, F, D), mk(H, F, D), pairs,
            jnp.asarray(rng.integers(0, 12, (clients, n, K))), jnp.abs(
                mk(clients, n, K)))


def _loop_over_experts(x, idx, weight, w_up, w_down, pairs, scale):
    """Every held expert over every token, masked by the routing."""
    out = 0.0
    for e in range(H):
        w_e = jnp.sum(jnp.where(idx == FIRST + e, weight, 0.0), -1)
        up = x @ w_up[e].T + scale * (x @ pairs.up_a[e]) @ pairs.up_b[e]
        hidden = jnp.square(jnp.maximum(up, 0.0))
        out = out + w_e[:, None] * (hidden @ w_down[e] + scale * (
            hidden @ pairs.down_a[e]) @ pairs.down_b[e])
    return out


@pytest.mark.parametrize("rows", [16])
def test_the_relu2_held_product_is_the_loop_over_experts(rows):
    """Two matrices and two pairs an expert, no gate: output and every
    gradient (``x``, the routing weights, the four pairs) of the batched call
    equal each client's loop over the held experts; at chunks of 16 rows a
    client takes three or more; ``None`` stands where a gated expert has its
    gate's pair."""
    x, w_up, w_down, pairs, idx, weight = _relu2_operands(clients=2)

    def ours(x, weight, pairs, idx):
        held = ep.sort_held(idx, H, FIRST)
        return ep.held_lora_products(x, weight, held, w_up, w_down, pairs,
                                     2.0, rows, form="relu2")

    def loss(x, weight, pairs, idx, fn):
        return jnp.sum(fn(x, weight, pairs, idx) ** 2)

    plain = lambda x, w, p, i: _loop_over_experts(  # noqa: E731
        x, i, w, w_up, w_down, p, 2.0)
    with jax.default_matmul_precision("highest"):
        batched, computed, further = jax.vmap(ours)(x, weight, pairs, idx)
        grads = jax.vmap(jax.grad(loss, (0, 1, 2)), in_axes=(
            0, 0, 0, 0, None))(x, weight, pairs, idx,
                               lambda *a: ours(*a)[0])
        for c in range(x.shape[0]):
            one = jax.tree.map(lambda a: a[c], (x, weight, pairs, idx))
            want = plain(*one)
            np.testing.assert_allclose(batched[c], want, atol=1e-5 * float(
                jnp.abs(want).max()))
            want_grads = jax.grad(loss, (0, 1, 2))(*one, plain)
            got = jax.tree.map(lambda a: a[c], grads)
            assert got[2].gate_a is None and got[2].gate_b is None
            assert _relative(got, want_grads) < 1e-5
    held = jax.vmap(lambda i: ep.sort_held(i, H, FIRST).counts)(idx).sum(-1)
    np.testing.assert_array_equal(computed, held)
    assert (int(further.max()) >= 2) == (rows == 16)


def test_an_unknown_form_is_refused():
    x, w_up, w_down, pairs, idx, weight = jax.tree.map(
        lambda a: a[0], _relu2_operands(clients=1))
    with pytest.raises(ValueError, match="form"):
        ep.held_lora_products(x, weight, ep.sort_held(idx, H, FIRST), w_up,
                              w_down, pairs, 2.0, 16, form="gelu")


def test_the_swiglu_form_lowers_to_what_it_lowered_to_before():
    """The gated form (K-EXAONE's: ``[H, d, 2f]`` gate and up, three pairs)
    under ``vmap`` and ``value_and_grad``: ``tests/fixtures/
    held_lora_swiglu_text.json`` holds the SHA-256 of this text as PR 39's
    parent (b5858dc) lowered it, the same function run in a copy of that
    commit: the form is a parameter and the gated program is bit for bit
    what it was."""
    rng = np.random.default_rng(0)
    mk = lambda *s: jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)  # noqa: E731
    f, clients, n = 8, 3, 48
    pairs = ep.ExpertPairs(mk(clients, H, D, R), mk(clients, H, R, f),
                           mk(clients, H, D, R), mk(clients, H, R, f),
                           mk(clients, H, f, R), mk(clients, H, R, D))
    x, w_gate_up, w_down = mk(clients, n, D), mk(H, D, 2 * f), mk(H, f, D)
    idx = jnp.asarray(rng.integers(0, 12, (clients, n, K)))
    weight = jnp.abs(mk(clients, n, K))

    def loss(x, weight, pairs, idx):
        held = ep.sort_held(idx, H, FIRST)
        return jnp.sum(ep.held_lora_products(
            x, weight, held, w_gate_up, w_down, pairs, 2.0, 16)[0] ** 2)

    text = jax.jit(jax.vmap(jax.value_and_grad(loss, (0, 1, 2)))).lower(
        x, weight, pairs, idx).as_text()
    with open(os.path.join(ROOT, "tests", "fixtures",
                           "held_lora_swiglu_text.json")) as f:
        want = json.load(f)["sha256"]
    assert hashlib.sha256(text.encode()).hexdigest() == want


# --- the gated norm in groups ---------------------------------------------------

@pytest.mark.parametrize("groups", [1, 4, 8])
def test_the_grouped_gated_norm_is_its_definition(groups):
    """A mean square over each run of ``channels / groups``, then the weight
    of all the channels; at one group it IS ``rms_norm`` (Granite's program
    keeps that call: not a reshape more)."""
    rng = np.random.default_rng(groups)
    x = jnp.asarray(rng.normal(size=(2, 5, 64)) * 3.0, jnp.float32)
    w = jnp.asarray(rng.normal(size=(64,)), jnp.float32)
    got = group_rms_norm(x, w, 1e-5, groups)
    runs = np.asarray(x).reshape(2, 5, groups, 64 // groups)
    want = (runs / np.sqrt((runs ** 2).mean(-1, keepdims=True) + 1e-5)
            ).reshape(2, 5, 64) * np.asarray(w)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    if groups == 1:
        np.testing.assert_array_equal(got, rms_norm(x, w, 1e-5))
        text = str(jax.make_jaxpr(lambda a: group_rms_norm(a, w, 1e-5))(x))
        assert text == str(jax.make_jaxpr(lambda a: rms_norm(a, w, 1e-5))(x))
    else:   # the groups differ, so one norm over all of them is another
        assert float(jnp.abs(got - rms_norm(x, w, 1e-5)).max()) > 1e-3


# --- the factory and the base -----------------------------------------------------

def test_the_factory_takes_the_sources_keys_and_refuses_what_it_cannot_run():
    model = nemotron_h(**CFG)
    assert model.cfg.kinds[5] == "attn" and model.cfg.head_dim == 16
    assert model.cfg.attention_multiplier == 16 ** -0.5
    # the published string may be longer than the stack that is held
    cut = nemotron_h(**{**CFG, "num_hidden_layers": 3})
    assert cut.cfg.kinds == ("mamba", "moe", "mamba")
    assert nemotron_h(num_classes=97, **{
        k: v for k, v in CFG.items() if k != "vocab_size"}).cfg.vocab_size == 97
    for key, other in (("mlp_hidden_act", "silu"), ("use_bias", True),
                       ("n_group", 2), ("tie_word_embeddings", True),
                       ("time_step_max", 0.5)):
        with pytest.raises(NotImplementedError, match=key):
            nemotron_h(**{**CFG, key: other})
    with pytest.raises(TypeError, match="unknown keys"):
        nemotron_h(**CFG, rotary=True)
    with pytest.raises(ValueError, match="unknown kinds"):
        nemotron_h(**{**CFG, "hybrid_override_pattern": "MEMEM-EME"})
    with pytest.raises(ValueError, match="names 9 blocks"):
        nemotron_h(**{**CFG, "num_hidden_layers": 10})
    with pytest.raises(ValueError, match="not among the 16"):
        nemotron_h(**{**CFG, "first_expert_held": 12})
    with pytest.raises(ValueError, match="groups"):
        nemotron_h(**{**CFG, "n_groups": 3})
    from fedml_tpu.models.registry import create_model

    assert type(create_model("nemotron_h", **CFG)) is type(model)


def test_the_drawn_base_follows_the_assumed_laws(reference):
    """``init_base``: the model's own tree (names and shapes), every input
    map and the router normal(0, 0.02), the embedding normal(0, 1), a block's
    output map normal(0, 0.001), the head normal(0, 0.002), norms and ``D`` 1,
    the decays and steps inside their stated ranges, the same seed the same
    tree; ``balance_router`` spreads a drawn router."""
    cfg = {**CFG, "hidden_size": 64, "mamba_num_heads": 8,
           "mamba_head_dim": 16, "vocab_size": 257, "base_dtype": "float32",
           "num_hidden_layers": 3}
    base = reference.init_base(cfg, 3900000001)
    model = nemotron_h(**cfg)
    ids = jnp.zeros((1, 8), jnp.int32)
    own, _ = split_frozen(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), ids))["params"])
    assert {p: a.shape for p, a in flatten_dict(base).items()} == {
        p: a.shape for p, a in flatten_dict(own).items()}
    flat = flatten_dict(base)
    for path, a in flat.items():
        a = np.asarray(a, np.float64)
        if path[-1] in ("norm", "final_norm", "norm_weight", "D"):
            assert (a == 1).all(), path
        elif path[-1] == "lm_head":
            assert a.std() == pytest.approx(0.002, rel=0.05)
        elif path[-1] == "embed":
            assert a.std() == pytest.approx(1.0, rel=0.05)
        elif path[-1] in ("out_proj", "o_proj", "down_proj", "experts_down"):
            assert a.std() == pytest.approx(0.001, rel=0.1), path
        elif path[-1] == "A_log":
            assert (np.exp(a) >= 1).all() and (np.exp(a) <= 16).all()
        elif path[-1] == "dt_bias":
            step = np.log1p(np.exp(a))
            assert (step >= 1e-3 * 0.999).all() and (step <= 0.1001).all()
        elif path[-1].startswith("conv_"):
            assert np.abs(a).max() <= 0.5
        elif path[-1] == "router_bias":
            assert a.std() == pytest.approx(0.05, rel=0.5)
        else:
            assert a.std() == pytest.approx(0.02, rel=0.1), path
    again = reference.init_base(cfg, 3900000001)
    assert all((a == b).all() for a, b in zip(jax.tree.leaves(base),
                                              jax.tree.leaves(again)))
    tokens = np.random.default_rng(0).integers(1, 257, (4, 64))
    balanced, found = reference.balance_router(_spread_router(base), cfg,
                                               tokens)
    assert len(found) == 1 and all(after <= before and after < 1.6
                                   for before, after in found)
    assert float(jnp.abs(balanced["layer_1"]["moe"]["router_bias"]).max()) > 0
