"""Operations and bytes of the K-EXAONE configuration's layers in the
federated ADAPTER round, as functions of the configuration file and the mix
file and of nothing the program does.

A product of ``[m, k] x [k, n]`` is ``2 m k n`` operations. The base is
frozen: a product with a frozen matrix is computed forward and backward with
respect to the ACTIVATIONS only, twice the forward's operations; a product of
two activations (the attention cores) or with a trained matrix (the low-rank
pairs) takes both gradients, three times the forward's. What a
rematerialising program computes again is not counted, nor are rows that a
padded layout adds. This shard's routed experts are counted at their
expectation under a flat router: ``top_k held / experts`` held assignments a
token. Bytes are the least traffic with the device's memory a kernel needs in
the step's dtype (bf16: 2 bytes), each operand read and each result written
once a pass.

The readers under ``layer_metrics/`` divide these by a scope's device time;
``configs/k_exaone_236b_a23b.json`` freezes ``train_flops_per_sample`` at
``train_flops_per_sequence`` (tests/test_benchmark_lm.py holds the two
together).
"""

from __future__ import annotations

BYTES = 2           # bf16 operands of the client step
FROZEN = 2          # forward + the activations' gradient, in forwards
TRAINED = 3         # forward + both operands' gradients, in forwards


def shapes(config: dict) -> dict:
    """The model's sizes as it is run: the factory's keyword arguments."""
    return config["factory_kwargs"]


def tokens(mix: dict) -> int:
    return int(mix["sequence_length"])


def layers(s: dict) -> dict:
    """How many of the held layers are ``window`` / ``full`` attention and
    ``dense`` / ``sparse`` feed-forward."""
    n = s["num_hidden_layers"]
    kinds, mlps = list(s["layer_types"])[:n], list(s["mlp_layer_types"])[:n]
    return {"window": kinds.count("sliding_attention"),
            "full": kinds.count("full_attention"),
            "dense": mlps.count("dense"), "sparse": mlps.count("sparse")}


def linears(s: dict) -> dict:
    """``{part: [(inputs, outputs)]}``: the linear projections of an
    attention layer, the dense MLP, and ONE expert (shared or routed), each
    with a low-rank pair."""
    d, hd = s["hidden_size"], s["head_dim"]
    q, kv = s["num_attention_heads"] * hd, s["num_key_value_heads"] * hd

    def mlp(f):
        return [(d, f), (d, f), (f, d)]

    return {"attn": [(d, q), (d, kv), (d, kv), (q, d)],
            "dense": mlp(s["intermediate_size"]),
            "expert": mlp(s["moe_intermediate_size"])}


def held_per_token(s: dict) -> float:
    """Held assignments a token at a flat router's expectation."""
    return s["num_experts_per_tok"] * s["num_experts_held"] / s["num_experts"]


# --- the kernels ------------------------------------------------------------

def _visible(t: int, window: int) -> int:
    """Pairs ``(i, j)`` with ``0 <= i - j < window`` among ``t`` tokens."""
    w = min(window or t, t)
    return w * (w + 1) // 2 + (t - w) * w


def attn_core_forward(s: dict, t: int, window: int) -> tuple:
    """``(operations, bytes)`` of softmax attention of one sequence in one
    layer: ``q k^T`` and ``p v`` over the VISIBLE pairs a query head (a
    window's band or the causal half). Bytes: q and the output of every
    query head, k and v of the key-value heads."""
    hq, hkv, hd = (s["num_attention_heads"], s["num_key_value_heads"],
                   s["head_dim"])
    ops = hq * 4 * hd * _visible(t, window)
    moved = t * (2 * hq + 2 * hkv) * hd * BYTES
    return ops, moved


def held_experts_forward(s: dict, t: int) -> tuple:
    """``(frozen operations, pairs' operations, activation bytes, matrix
    bytes)`` of the held experts' product over one sequence in one layer: the
    real assignments' three frozen products and their three pairs; bytes of
    the assignments' rows read and results written, and of every held
    expert's three matrices."""
    d, f, r = s["hidden_size"], s["moe_intermediate_size"], s["adapter_rank"]
    rows = t * held_per_token(s)
    frozen = rows * 2 * 3 * d * f
    low = rows * 2 * r * 3 * (d + f)
    moved = rows * 2 * d * BYTES
    return frozen, low, moved, s["num_experts_held"] * 3 * d * f * BYTES


# --- the whole step ---------------------------------------------------------

def forward_flops_per_token(s: dict, t: int) -> dict:
    """Operations a token of the forward pass, by part, at sequence length
    ``t``: the attention layers' projections, the dense layer's MLP, the
    shared experts, the held routed experts at their expectation, the
    routers, the head (all with a frozen operand), the attention cores and
    the low-rank pairs."""
    n, lin, r = layers(s), linears(s), s["adapter_rank"]

    def frozen(pairs):
        return sum(2 * i * o for i, o in pairs)

    def low_rank(pairs):
        return sum(2 * r * (i + o) for i, o in pairs)

    attn_layers = n["window"] + n["full"]
    per_token = held_per_token(s)
    return {
        "attn_projections": attn_layers * frozen(lin["attn"]),
        "dense_mlp": n["dense"] * frozen(lin["dense"]),
        "shared_experts": n["sparse"] * frozen(lin["expert"]),
        "held_experts": n["sparse"] * per_token * frozen(lin["expert"]),
        "router": n["sparse"] * 2 * s["hidden_size"] * s["num_experts"],
        "head": 2 * s["hidden_size"] * s["vocab_size"],
        "attn_core": (n["window"] * attn_core_forward(
            s, t, s["sliding_window"])[0] + n["full"] * attn_core_forward(
                s, t, 0)[0]) / t,
        "lora": (attn_layers * low_rank(lin["attn"])
                 + n["dense"] * low_rank(lin["dense"])
                 + n["sparse"] * (1 + per_token) * low_rank(lin["expert"])),
    }


def train_flops_per_sequence(config: dict, mix: dict) -> int:
    """What ``train_flops_per_sample`` freezes: a sample is one packed
    sequence; products with a frozen operand twice their forward, the cores
    and the pairs three times."""
    per_token = forward_flops_per_token(shapes(config), tokens(mix))
    return int(round(tokens(mix) * sum(
        (TRAINED if part in ("attn_core", "lora") else FROZEN) * ops
        for part, ops in per_token.items())))


def parameters(s: dict) -> dict:
    """``{"base", "adapters"}``: the frozen parameters (norms and the
    router's selection bias included) and the low-rank pairs'."""
    d, r, n, lin = s["hidden_size"], s["adapter_rank"], layers(s), linears(s)
    held = s["num_experts_held"]

    def frozen(pairs):
        return sum(i * o for i, o in pairs)

    def low_rank(pairs):
        return sum(r * (i + o) for i, o in pairs)

    attn_layers = n["window"] + n["full"]
    # q and k norms a head, the two norms on the branches' outputs
    attn = frozen(lin["attn"]) + 2 * s["head_dim"] + 2 * d
    sparse = ((1 + held) * frozen(lin["expert"])
              + d * s["num_experts"] + s["num_experts"])
    return {
        "base": (attn_layers * attn + n["dense"] * frozen(lin["dense"])
                 + n["sparse"] * sparse + 2 * s["vocab_size"] * d + d),
        "adapters": (attn_layers * low_rank(lin["attn"])
                     + n["dense"] * low_rank(lin["dense"])
                     + n["sparse"] * (1 + held) * low_rank(lin["expert"])),
    }


# --- a round ----------------------------------------------------------------

def steps_per_client(mix: dict) -> int:
    per_client = -(-int(mix["counts"]["per_client"]) // int(mix["batch"]))
    return per_client * int(mix["epochs"])


def steps_per_round(mix: dict) -> int:
    """Local steps a round: every sampled client's sequences, a batch at a
    time, ``epochs`` times."""
    return int(mix["cohort"]) * steps_per_client(mix)


def roofline_ms_per_round(kernel: str, config: dict, mix: dict,
                          peaks: dict) -> float:
    """The least time a round's calls of ``kernel`` could take on a chip
    with ``peaks``: the larger of its operations over the peak rate and its
    bytes over the memory's, forward and backward, over every layer that
    has it and every step of the round. The held experts' matrices are
    counted once a pass (forward, backward) of each of a client's steps, not
    once a client: the cohort's clients take their steps side by side."""
    s, t = shapes(config), tokens(mix)
    n = layers(s)
    batch, steps = int(mix["batch"]), steps_per_round(mix)
    if kernel == "attn_window":
        ops, moved = attn_core_forward(s, t, s["sliding_window"])
        ops, moved = (TRAINED * batch * steps * n["window"] * v
                      for v in (ops, moved))
    elif kernel == "moe_held_lora":
        frozen, low, moved, matrices = held_experts_forward(s, t)
        ops = batch * steps * n["sparse"] * (FROZEN * frozen + TRAINED * low)
        moved = n["sparse"] * (FROZEN * batch * steps * moved
                               + FROZEN * steps_per_client(mix) * matrices)
    else:
        raise KeyError(f"no counted kernel {kernel!r}")
    return 1e3 * max(ops / peaks["bf16_flops_per_s"],
                     moved / peaks["hbm_bytes_per_s"])
