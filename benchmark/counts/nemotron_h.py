"""Operations and bytes of the Nemotron-H configuration's blocks in the
federated ADAPTER round, as functions of the configuration file and the mix
file and of nothing the program does.

A product of ``[m, k] x [k, n]`` is ``2 m k n`` operations. The base is
frozen: a product with a frozen matrix is computed forward and backward with
respect to the ACTIVATIONS only, twice the forward's operations; a product of
two activations (the scan, the attention core) or with a trained matrix (the
low-rank pairs) takes both gradients, three times the forward's. What a
rematerialising program computes again is not counted, nor are rows or columns
that a padded layout adds. This shard's routed experts are counted at their
expectation under a flat router: ``top_k held / experts`` held assignments a
token. Bytes are the least traffic with the device's memory a kernel needs in
the step's dtype (bf16: 2 bytes), each operand read and each result written
once a pass.

The readers under ``layer_metrics/`` divide these by a scope's device time;
``configs/nemotron_3_nano_30b_a3b.json`` freezes ``train_flops_per_sample`` at
``train_flops_per_sequence`` (tests/test_benchmark_lm.py holds the two
together).
"""

from __future__ import annotations

BYTES = 2           # bf16 operands of the client step
FROZEN = 2          # forward + the activations' gradient, in forwards
TRAINED = 3         # forward + both operands' gradients, in forwards
KINDS = {"M": "mamba", "*": "attn", "E": "moe"}


def shapes(config: dict) -> dict:
    """The model's sizes as it is run: the factory's keyword arguments."""
    return config["factory_kwargs"]


def tokens(mix: dict) -> int:
    return int(mix["sequence_length"])


def layers(s: dict) -> dict:
    """How many of the held blocks are ``mamba``, ``attn`` and ``moe``."""
    kinds = [KINDS[ch] for ch in s["hybrid_override_pattern"][
        :s["num_hidden_layers"]]]
    return {kind: kinds.count(kind) for kind in KINDS.values()}


def conv_dim(s: dict) -> int:
    return (s["mamba_num_heads"] * s["mamba_head_dim"]
            + 2 * s["n_groups"] * s["ssm_state_size"])


def linears(s: dict) -> dict:
    """``{part: [(inputs, outputs)]}``: the linear maps of a Mamba-2 block,
    an attention block, the shared expert and ONE routed expert, each with a
    low-rank pair."""
    d, hd = s["hidden_size"], s["head_dim"]
    inner = s["mamba_num_heads"] * s["mamba_head_dim"]
    q, kv = s["num_attention_heads"] * hd, s["num_key_value_heads"] * hd

    def mlp(f):
        return [(d, f), (f, d)]

    return {"mamba": [(d, inner + conv_dim(s) + s["mamba_num_heads"]),
                      (inner, d)],
            "attn": [(d, q), (d, kv), (d, kv), (q, d)],
            "shared": mlp(s["moe_shared_expert_intermediate_size"]),
            "expert": mlp(s["moe_intermediate_size"])}


def held_per_token(s: dict) -> float:
    """Held assignments a token at a flat router's expectation."""
    return (s["num_experts_per_tok"] * s["num_experts_held"]
            / s["n_routed_experts"])


# --- the kernels ------------------------------------------------------------

def ssm_scan_forward(s: dict, t: int) -> tuple:
    """``(operations, bytes)`` of Mamba-2's recurrence over one sequence of
    ``t`` tokens in one block, as the recurrence states it: a head a token
    decays the state (P N), scales its input by ``dt`` (P), adds the
    rank-one update (2 P N) and reads the state with C (2 P N). Bytes: x and
    y of every head, B and C of the ``n_groups`` groups, ``dt`` in float32."""
    h, p, n, g = (s["mamba_num_heads"], s["mamba_head_dim"],
                  s["ssm_state_size"], s["n_groups"])
    ops = t * h * (5 * p * n + p)
    moved = t * (2 * h * p + 2 * g * n) * BYTES + t * h * 4
    return ops, moved


def attn_core_forward(s: dict, t: int) -> tuple:
    """Causal softmax attention of one sequence in one block: ``q k^T`` and
    ``p v`` over the ``t (t + 1) / 2`` visible pairs a query head. Bytes: q
    and the output of every query head, k and v of the key-value heads."""
    hq, hkv, hd = (s["num_attention_heads"], s["num_key_value_heads"],
                   s["head_dim"])
    ops = hq * 4 * hd * t * (t + 1) // 2
    moved = t * (2 * hq + 2 * hkv) * hd * BYTES
    return ops, moved


def held_experts_forward(s: dict, t: int) -> tuple:
    """``(frozen operations, pairs' operations, activation bytes, matrix
    bytes)`` of the held experts' product over one sequence in one block: the
    real assignments' two frozen products and their two pairs; bytes of the
    assignments' rows read and results written, and of every held expert's
    two matrices at the published width."""
    d, f, r = s["hidden_size"], s["moe_intermediate_size"], s["adapter_rank"]
    rows = t * held_per_token(s)
    frozen = rows * 2 * 2 * d * f
    low = rows * 2 * r * 2 * (d + f)
    moved = rows * 2 * d * BYTES
    return frozen, low, moved, s["num_experts_held"] * 2 * d * f * BYTES


# --- the whole step ---------------------------------------------------------

def forward_flops_per_token(s: dict, t: int) -> dict:
    """Operations a token of the forward pass, by part, at sequence length
    ``t``: the Mamba-2 and attention blocks' projections, the shared experts,
    the held routed experts at their expectation, the routers, the
    convolutions and the head (all with a frozen operand), the scans, the
    attention core and the low-rank pairs."""
    n, lin, r = layers(s), linears(s), s["adapter_rank"]

    def frozen(pairs):
        return sum(2 * i * o for i, o in pairs)

    def low_rank(pairs):
        return sum(2 * r * (i + o) for i, o in pairs)

    per_token = held_per_token(s)
    return {
        "mamba_projections": n["mamba"] * frozen(lin["mamba"]),
        "attn_projections": n["attn"] * frozen(lin["attn"]),
        "shared_experts": n["moe"] * frozen(lin["shared"]),
        "held_experts": n["moe"] * per_token * frozen(lin["expert"]),
        "router": n["moe"] * 2 * s["hidden_size"] * s["n_routed_experts"],
        "conv": n["mamba"] * 2 * s["conv_kernel"] * conv_dim(s),
        "head": 2 * s["hidden_size"] * s["vocab_size"],
        "ssm_scan": n["mamba"] * ssm_scan_forward(s, t)[0] / t,
        "attn_core": n["attn"] * attn_core_forward(s, t)[0] / t,
        "lora": (n["mamba"] * low_rank(lin["mamba"])
                 + n["attn"] * low_rank(lin["attn"])
                 + n["moe"] * (low_rank(lin["shared"])
                               + per_token * low_rank(lin["expert"]))),
    }


def train_flops_per_sequence(config: dict, mix: dict) -> int:
    """What ``train_flops_per_sample`` freezes: a sample is one packed
    sequence; products with a frozen operand twice their forward, the scans,
    the cores and the pairs three times."""
    per_token = forward_flops_per_token(shapes(config), tokens(mix))
    return int(round(tokens(mix) * sum(
        (TRAINED if part in ("ssm_scan", "attn_core", "lora") else FROZEN)
        * ops for part, ops in per_token.items())))


def parameters(s: dict) -> dict:
    """``{"base", "adapters"}``: the frozen parameters (norms, the
    convolutions and the routers' selection biases included) and the
    low-rank pairs'."""
    d, r, n, lin = s["hidden_size"], s["adapter_rank"], layers(s), linears(s)
    h, held = s["mamba_num_heads"], s["num_experts_held"]
    inner = h * s["mamba_head_dim"]

    def frozen(pairs):
        return sum(i * o for i, o in pairs)

    def low_rank(pairs):
        return sum(r * (i + o) for i, o in pairs)

    # convolution and its bias, A_log, dt_bias, D, the gated norm
    mamba_small = (s["conv_kernel"] + 1) * conv_dim(s) + 3 * h + inner
    sparse = (held * frozen(lin["expert"]) + frozen(lin["shared"])
              + d * s["n_routed_experts"] + s["n_routed_experts"])
    blocks = sum(n.values())
    return {
        "base": (n["mamba"] * (frozen(lin["mamba"]) + mamba_small)
                 + n["attn"] * frozen(lin["attn"]) + n["moe"] * sparse
                 + blocks * d               # a block's input norm
                 + 2 * s["vocab_size"] * d + d),
        "adapters": (n["mamba"] * low_rank(lin["mamba"])
                     + n["attn"] * low_rank(lin["attn"])
                     + n["moe"] * (low_rank(lin["shared"])
                                   + held * low_rank(lin["expert"]))),
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
    bytes over the memory's, forward and backward, over every block that
    has it and every step of the round. The held experts' matrices are
    counted once a pass (forward, backward) of each of a client's steps, not
    once a client: the cohort's clients take their steps side by side."""
    s, t = shapes(config), tokens(mix)
    n = layers(s)
    batch, steps = int(mix["batch"]), steps_per_round(mix)
    if kernel == "ssm_groups_scan":
        ops, moved = (TRAINED * batch * steps * n["mamba"] * v
                      for v in ssm_scan_forward(s, t))
    elif kernel == "moe_relu2":
        frozen, low, moved, matrices = held_experts_forward(s, t)
        ops = batch * steps * n["moe"] * (FROZEN * frozen + TRAINED * low)
        moved = n["moe"] * (FROZEN * batch * steps * moved
                            + FROZEN * steps_per_client(mix) * matrices)
    else:
        raise KeyError(f"no counted kernel {kernel!r}")
    return 1e3 * max(ops / peaks["bf16_flops_per_s"],
                     moved / peaks["hbm_bytes_per_s"])
