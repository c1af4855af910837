"""MiniMax-M3 (``models/minimax_m3.py``) against its plain reference
(``benchmark/reference_minimax_m3.py``) on seeded weights: logits, loss and
every adapter's gradient through the selected-block kernels and through the
masked softmax; the indexer's choices (the first and the own block always, a
tie to the lower block); the kernels against the plain masked attention,
forward and backward, under ``vmap``; the shares of a 16-way expert-parallel
layer summed; the fused cross-entropy; the factory. CPU, small sizes."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.models.adapter import merge_params, split_frozen
from fedml_tpu.models.minimax_m3 import (SparseMoE, minimax_m3, select_blocks,
                                         token_ce)
from fedml_tpu.ops import block_sparse_attention as bsa
from fedml_tpu.trainer.local import seq_softmax_ce

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the source's keys at the CPU tests' sizes: hidden 32, the dense layer and
#: an expert layer, 4 query heads over 2 key-value heads of 16 turning 8
#: dims, blocks of 8 tokens and a top-2 of an indexer of 2 heads a group, 16
#: experts top-3 of which 4 (experts 4-7) are held
SMALL = dict(
    vocab_size=97, hidden_size=32, num_hidden_layers=2, moe_layer_freq=[0, 1],
    num_attention_heads=4, num_key_value_heads=2, head_dim=16, rotary_dim=8,
    intermediate_size=12, dense_intermediate_size=40,
    shared_intermediate_size=12, num_local_experts=16, num_experts_per_tok=3,
    num_experts_held=4, first_expert_held=4, msa_index_heads=2,
    msa_block_size=8, msa_topk=2, adapter_rank=4, adapter_alpha=8.0,
    adapter_b_std=0.01)
#: the rest of the source's dictionary, as published
PUBLISHED = dict(
    max_position_embeddings=1048576, rms_norm_eps=1e-06, use_gemma_norm=True,
    attention_output_gate=False, rope_theta=5000000, partial_rotary_factor=0.5,
    hidden_act="swigluoai", use_qk_norm=True, tie_word_embeddings=False,
    n_shared_experts=1, scoring_func="sigmoid", use_routing_bias=True,
    qk_norm_type="per_head", num_mtp_modules=7, num_nextn_predict_layers=1,
    swiglu_alpha=1.702, swiglu_limit=7, routed_scaling_factor=2)
CFG = {**PUBLISHED, **SMALL}
#: six blocks: a token of blocks 4 and 5 has more candidates than the top-2
T = 48


def _reference():
    spec = importlib.util.spec_from_file_location(
        "test_reference_minimax_m3",
        os.path.join(ROOT, "benchmark", "reference_minimax_m3.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def reference():
    return _reference()


def _ids(batch=1, seed=0):
    ids = jax.random.randint(jax.random.PRNGKey(seed), (batch, T), 1, 97)
    return ids, jnp.concatenate(
        [ids[:, 1:], jnp.zeros((batch, 1), jnp.int32)], axis=1)


@pytest.fixture(scope="module")
def seeded(reference):
    """``(cfg, base, adapters)``: the reference's drawn base at the tests'
    size (float32), the routers spread as the published width's are, the
    output maps at 0.05 so that each branch moves the stream, and the
    model's own adapters."""
    cfg = dict(CFG, base_dtype="float32")
    base = reference.init_base(cfg, 3)

    def widen(tree):
        out = {}
        for k, v in tree.items():
            if isinstance(v, dict):
                out[k] = widen(v)
            elif k in ("o_proj", "down_proj", "experts_down"):
                out[k] = v * 50.0
            elif k == "router":
                out[k] = v * 12.0
            else:
                out[k] = v
        return out

    model = minimax_m3(**CFG, base_dtype="float32")
    params = jax.jit(lambda r, x: model.init({"params": r}, x))(
        jax.random.PRNGKey(1), _ids()[0])["params"]
    return cfg, widen(base), split_frozen(params)[1]


def _relative(got, want):
    num = sum(float(jnp.sum((g - w) ** 2)) for g, w in zip(
        jax.tree.leaves(got), jax.tree.leaves(want)))
    den = sum(float(jnp.sum(w ** 2)) for w in jax.tree.leaves(want))
    return (num / den) ** 0.5


# --- the model against the reference ----------------------------------------

def test_the_tree_holds_what_the_reference_draws(seeded):
    """Every frozen tensor of the model's tree has the reference's name and
    shape; the pairs sit beside every projection but the indexer's."""
    _, base, adapters = seeded
    model = minimax_m3(**CFG, base_dtype="float32")
    mine, pairs = split_frozen(jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32)))["params"])
    assert jax.tree.structure(mine) == jax.tree.structure(base)
    assert jax.tree.all(jax.tree.map(lambda a, b: a.shape == b.shape, mine,
                                     base))
    assert "lora_index_proj_a" not in pairs["layer_1"]["attn"]
    assert {"lora_q_proj_a", "lora_k_proj_b", "lora_o_proj_a"} <= set(
        pairs["layer_1"]["attn"])


def test_float32_logits_loss_and_every_adapter_gradient(seeded, reference):
    """Through the selected-block kernels (interpreted) the model gives the
    reference's logits, loss and gradients of every pair."""
    cfg, base, adapters = seeded
    model = minimax_m3(**CFG, base_dtype="float32")
    ids, labels = _ids()
    with jax.default_matmul_precision("highest"):
        def loss(a):
            logits = model.apply({"params": merge_params(base, a)}, ids)
            return jnp.mean(token_ce(logits, labels)), logits

        (got, logits), grads = jax.jit(jax.value_and_grad(
            loss, has_aux=True))(adapters)
        want = reference.logits(base, adapters, ids[0], cfg)[None]
        ref_loss, ref_grads = jax.value_and_grad(
            lambda a: reference.batch_loss(a, base, ids, labels, cfg))(
                adapters)
    np.testing.assert_allclose(logits, want, atol=2e-5 * float(
        jnp.abs(want).max()))
    assert abs(float(got) - float(ref_loss)) < 1e-5
    assert _relative(grads, ref_grads) < 1e-4


def test_the_fused_cross_entropy_is_the_optax_one():
    """``token_ce``'s own backward (the logits and their log-sum-exp kept)
    gives ``seq_softmax_ce``'s loss and gradient, pad labels left out."""
    logits = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 31)) * 3.0
    labels = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, 31)
    for fn in (token_ce, seq_softmax_ce):
        assert fn(logits, labels).shape == (2,)
    np.testing.assert_allclose(token_ce(logits, labels),
                               seq_softmax_ce(logits, labels), rtol=1e-6)
    np.testing.assert_allclose(
        jax.grad(lambda z: jnp.sum(token_ce(z, labels)))(logits),
        jax.grad(lambda z: jnp.sum(seq_softmax_ce(z, labels)))(logits),
        atol=1e-6)


# --- the indexer's choices ---------------------------------------------------

def _keys(seed, n_tokens=T, groups=2, heads=2, dim=16):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(k1, (1, n_tokens, groups, heads, dim)),
            jax.random.normal(k2, (1, n_tokens, groups, dim)))


@pytest.mark.parametrize("case", ["scored", "tied", "every_block"])
def test_the_choices_are_the_references(reference, case):
    """``select_blocks`` keeps what the reference keeps: block 0 and the
    token's own always, the top-k of the blocks between by the indexer's
    score, all of them where they are fewer (``every_block``: a top-6 of 6
    blocks), and the lower block of a tie (``tied``: every score 0)."""
    u, k = _keys(0)
    if case == "tied":
        u = jnp.zeros_like(u)
    topk = 6 if case == "every_block" else 2
    chosen = select_blocks(u, k, 8, topk)
    n = T // 8
    mine = np.asarray((chosen[0][..., None] == np.arange(n)).any(axis=2))
    cfg = {"msa_block_size": 8, "msa_index_heads": 2, "msa_topk": topk}
    base = {"index_proj": jnp.eye(64)}         # u is a's own columns
    with jax.default_matmul_precision("highest"):
        kept = reference.kept_blocks(base, u[0].reshape(T, 64), k[0], cfg)
    np.testing.assert_array_equal(mine, np.asarray(kept))
    own = np.arange(T) // 8
    assert mine[:, :, 0].all() and mine[np.arange(T), :, own].all()
    sizes = mine.sum(-1)[:, 0]
    np.testing.assert_array_equal(
        sizes, np.where(own == 0, 1, 2 + np.minimum(own - 1, topk)))
    if case == "tied":      # blocks 1 and 2 before any later one
        late = own == 5
        assert mine[late, :, 1:3].all() and not mine[late, :, 3:5].any()


# --- the kernels against the plain masked attention --------------------------

def _chosen(seed, batch, n_tokens, groups, block, topk):
    rng = np.random.default_rng(seed)
    out = -np.ones((batch, n_tokens, groups, 2 + topk), np.int32)
    for b in range(batch):
        for t in range(n_tokens):
            own = t // block
            for g in range(groups):
                out[b, t, g, 0] = 0
                if own:
                    out[b, t, g, 1] = own
                pick = rng.permutation(np.arange(1, own))[:topk]
                out[b, t, g, 2:2 + len(pick)] = pick
    return jnp.asarray(out)


def _listed(extra, slots):
    """Block 0 and the own block for every token of two clients, and
    ``extra[(client, group)] = {block: tokens}`` in ``slots`` more slots."""
    out = np.asarray(_chosen(0, 2, T, 2, 8, 0))
    out = np.concatenate(
        [out, -np.ones(out.shape[:-1] + (slots,), np.int32)], -1)
    for (b, g), picks in extra.items():
        for block, tokens in picks.items():
            for t in tokens:
                out[b, t, g, np.flatnonzero(out[b, t, g] < 0)[0]] = block
    return jnp.asarray(out)


#: a key block chosen by one token of a tile (a chunk of one token and three
#: padding rows, the last token of the sequence among them); a tile whose
#: blocks are chosen by 5, 7 (a chunk and a part) and all 8 of its tokens
#: (whole-tile visits beside compacted ones, off the own block)
LISTED = {
    "one_token": (1, {(0, 0): {1: [25], 2: [47]}, (0, 1): {3: [33]},
                      (1, 0): {4: [40]}, (1, 1): {1: [20]}}),
    "ragged_and_whole": (3, {
        (0, 0): {1: range(32, 37), 2: range(32, 40), 3: range(33, 40)},
        (0, 1): {2: range(40, 46), 4: [41, 43, 45]},
        (1, 0): {1: range(24, 31), 2: [26, 27]},
        (1, 1): {1: range(40, 48), 3: range(40, 45), 4: [47]}})}


@pytest.mark.parametrize("topk, dtype", [
    (6, jnp.float32), (2, jnp.float32), (0, jnp.float32),
    (2, jnp.bfloat16), ("one_token", jnp.float32),
    ("ragged_and_whole", jnp.float32)])
def test_the_kernels_are_the_masked_softmax(topk, dtype):
    """Output and the gradients of q, k and v of the interpreted kernels
    equal one softmax over [T, T] masked to the chosen blocks: every block
    chosen (a top-6 of 6 blocks: whole-tile visits only), two of the blocks
    between, none but the first and the own; the lists of ``LISTED``
    (chunks of 4 tokens: a block of one token, counts that fill no whole
    chunk, whole and compacted visits in one tile); two clients under
    ``vmap``; and in bf16 (float32 accumulation on both sides)."""
    rng = np.random.default_rng(1)
    mk = lambda *s: jnp.asarray(rng.normal(size=s), dtype)  # noqa: E731
    q, k, v = mk(2, 1, T, 4, 16), mk(2, 1, T, 2, 16), mk(2, 1, T, 2, 16)
    chosen = (_listed(*LISTED[topk][::-1]) if topk in LISTED
              else _chosen(2, 2, T, 2, 8, topk))[:, None]
    assert bsa.chunk(8, 2) == 4

    def loss(fn):
        return lambda q, k, v, c: jnp.sum(jnp.sin(
            fn(q, k, v, c, 8).astype(jnp.float32)))

    with jax.default_matmul_precision("highest"):
        got = jax.vmap(lambda *a: bsa.block_sparse_attention(*a, 8))(
            q, k, v, chosen)
        want = jax.vmap(lambda *a: bsa.plain_block_sparse_attention(*a, 8))(
            q, k, v, chosen)
        grads = jax.vmap(jax.grad(loss(bsa.block_sparse_attention),
                                  (0, 1, 2)))(q, k, v, chosen)
        want_grads = jax.vmap(jax.grad(loss(
            bsa.plain_block_sparse_attention), (0, 1, 2)))(q, k, v, chosen)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol * float(jnp.abs(want).max()))
    assert _relative(jax.tree.map(lambda a: a.astype(jnp.float32), grads),
                     jax.tree.map(lambda a: a.astype(jnp.float32),
                                  want_grads)) < tol


def test_the_counts_of_a_call():
    """``counts``: every visible pair of the chosen blocks (whole blocks
    before the own, the own up to the token), the pairs the kernels
    multiply and the blocks of the tiles' unions. With every block chosen
    the union is every block up to the tile's own and every visit takes the
    whole tile, so computed pairs are the causal half's blocks; with a few
    blocks chosen by some tokens of a tile (``few``), a visit multiplies its
    tokens padded to whole chunks of 4 (8 keys each)."""
    everything = _chosen(0, 1, T, 2, 8, 6)
    got = bsa.counts(jnp.swapaxes(everything, 1, 2), 8, 2)
    n = T // 8
    assert float(got["msa_pairs_selected"]) == 2 * T * (T + 1) / 2
    assert float(got["msa_blocks_fetched"]) == 2 * n * (n + 1) / 2
    assert float(got["msa_pairs_computed"]) == 2 * 64 * n * (n + 1) / 2
    first_and_own = bsa.counts(
        jnp.swapaxes(_chosen(0, 1, T, 2, 8, 0), 1, 2), 8, 2)
    assert float(first_and_own["msa_pairs_selected"]) == 2 * (
        n * 36 + (T - 8) * 8)
    # group 0: block 1 by token 25 (tile 3), block 2 by tokens 40-44 (tile
    # 5); group 1: block 3 by every token of tile 4
    few = _listed({(0, 0): {1: [25], 2: range(40, 45)},
                   (0, 1): {3: range(32, 40)}}, 1)[:1]
    got = bsa.counts(jnp.swapaxes(few, 1, 2), 8, 2)
    whole = 2 * (1 + 2 * (n - 1)) + 1        # first, own, and tile 4's block 3
    assert float(got["msa_blocks_fetched"]) == whole + 2
    assert float(got["msa_pairs_selected"]) == 2 * (n * 36 + (T - 8) * 8) + (
        1 + 5 + 8) * 8
    assert float(got["msa_pairs_computed"]) == whole * 64 + (4 + 8) * 8


# --- the shares of an expert-parallel layer ----------------------------------

def test_the_shares_of_a_16_way_layer_add_up(seeded, reference):
    """One expert layer of 32 experts cut into 16 shares of 2, each computed
    by the program as its own shard (``first_expert_held`` 0, 2, .., 30, the
    16-way layer of the benchmark's deployment at the tests' size), the
    shared expert counted once: the shares sum to the uncut reference's
    layer, and every assignment lies in exactly one share."""
    cfg, base, adapters = seeded
    moe = base["layer_1"]["moe"]
    rng = np.random.default_rng(0)
    def mk(*s):
        return jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)

    experts, f = 32, 12
    whole = dict(moe, router=mk(32, experts), router_bias=mk(experts) * 0.1,
                 experts_gate_up=mk(experts, 32, 2 * f),
                 experts_down=mk(experts, f, 32))
    pairs = {f"lora_experts_{n}": mk(experts, *s) for n, s in (
        ("gate_a", (32, 4)), ("gate_b", (4, f)), ("up_a", (32, 4)),
        ("up_b", (4, f)), ("down_a", (f, 4)), ("down_b", (4, 32)))}
    shared = adapters["layer_1"]["moe"]["shared"]
    x = mk(1, T, 32)
    uncut = dict(cfg, num_local_experts=experts, num_experts_held=experts,
                 first_expert_held=0)
    with jax.default_matmul_precision("highest"):
        want = reference.sparse_moe(whole, {**pairs, "shared": shared}, x[0],
                                    uncut)
        only_shared = reference.mlp(whole["shared"], shared, x[0], uncut)
        total, tokens = 0.0, 0.0
        for first in range(0, experts, 2):
            shapes = minimax_m3(**{**CFG, "num_local_experts": experts,
                                   "num_experts_held": 2,
                                   "first_expert_held": first},
                                base_dtype="float32").cfg
            cut = lambda a: a[first:first + 2]  # noqa: E731
            share = {**{k: cut(v) for k, v in pairs.items()},
                     "router": whole["router"],
                     "router_bias": whole["router_bias"],
                     "experts_gate_up": cut(whole["experts_gate_up"]),
                     "experts_down": cut(whole["experts_down"]),
                     "shared": {**whole["shared"], **shared}}
            out, state = SparseMoE(shapes, jnp.float32).apply(
                {"params": share}, x, mutable=["counters"])
            total = total + out[0] - only_shared
            tokens += float(state["counters"]["expert_tokens"].sum())
    np.testing.assert_allclose(total + only_shared, want, atol=1e-5 * float(
        jnp.abs(want).max()))
    assert tokens == T * 3


# --- the factory -------------------------------------------------------------

def test_the_factory_takes_the_sources_keys_and_refuses_what_it_cannot_run():
    model = minimax_m3(**CFG)
    assert model.cfg.moe_layer_freq == (0, 1)
    assert model.cfg.rotary_dim == 8 and model.cfg.num_local_experts == 16
    for key, value in (("hidden_act", "silu"), ("scoring_func", "softmax"),
                       ("use_gemma_norm", False), ("swiglu_limit", 10),
                       ("tie_word_embeddings", True),
                       ("attention_output_gate", True)):
        with pytest.raises(NotImplementedError, match=key):
            minimax_m3(**{**CFG, key: value})
    with pytest.raises(ValueError, match="partial_rotary_factor"):
        minimax_m3(**{**CFG, "partial_rotary_factor": 1.0})
    with pytest.raises(TypeError, match="unknown"):
        minimax_m3(**CFG, sliding_window=128)
    with pytest.raises(ValueError, match="held experts"):
        minimax_m3(**{**CFG, "num_experts_held": 16})
