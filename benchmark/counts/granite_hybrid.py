"""Operations and bytes of the Granite 4.0-H configuration's layers in the
federated ADAPTER round, as functions of the configuration file and the mix
file and of nothing the program does.

A product of ``[m, k] x [k, n]`` is ``2 m k n`` operations. The base is
frozen: a product with a frozen matrix is computed forward and backward with
respect to the ACTIVATIONS only, twice the forward's operations; a product of
two activations (the scan, the attention core) or with a trained matrix (the
low-rank pairs) takes both gradients, three times the forward's. What a
rematerialising program computes again is not counted. Bytes are the least
traffic with the device's memory a kernel needs in the step's dtype (bf16: 2
bytes), each operand read and each result written once a pass.

The readers under ``layer_metrics/`` divide these by a scope's device time;
``configs/granite_4_0_h_micro.json`` freezes ``train_flops_per_sample`` at
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


def layers(s: dict) -> tuple:
    """``(Mamba-2 layers, attention layers)``."""
    kinds = list(s["layer_types"])
    return kinds.count("mamba"), kinds.count("attention")


def linears(s: dict) -> tuple:
    """``([(inputs, outputs)] of a Mamba-2 mixer, of an attention mixer, of
    the MLP)``: the linear projections, each with a low-rank pair."""
    d = s["hidden_size"]
    inner = s["mamba_n_heads"] * s["mamba_d_head"]
    conv = inner + 2 * s["mamba_n_groups"] * s["mamba_d_state"]
    hd = d // s["num_attention_heads"]
    kv = s["num_key_value_heads"] * hd
    f = s["shared_intermediate_size"]
    return ([(d, inner + conv + s["mamba_n_heads"]), (inner, d)],
            [(d, d), (d, kv), (d, kv), (d, d)],
            [(d, 2 * f), (f, d)])


# --- the two kernels --------------------------------------------------------

def ssm_scan_forward(s: dict, t: int) -> tuple:
    """``(operations, bytes)`` of Mamba-2's recurrence over one sequence of
    ``t`` tokens in one layer, as the recurrence states it: a head a token
    decays the state (P N), scales its input by ``dt`` (P), adds the
    rank-one update (2 P N) and reads the state with C (2 P N). Bytes: x and
    y of every head, B and C of the groups, ``dt`` in float32."""
    h, p, n, g = (s["mamba_n_heads"], s["mamba_d_head"], s["mamba_d_state"],
                  s["mamba_n_groups"])
    ops = t * h * (5 * p * n + p)
    moved = t * (2 * h * p + 2 * g * n) * BYTES + t * h * 4
    return ops, moved


def attn_core_forward(s: dict, t: int) -> tuple:
    """Causal softmax attention of one sequence in one layer: ``q k^T`` and
    ``p v`` over the ``t (t + 1) / 2`` visible pairs a query head. Bytes: q
    and the output of every query head, k and v of the key-value heads."""
    hq, hkv = s["num_attention_heads"], s["num_key_value_heads"]
    hd = s["hidden_size"] // hq
    ops = hq * 4 * hd * t * (t + 1) // 2
    moved = t * (2 * hq + 2 * hkv) * hd * BYTES
    return ops, moved


# --- the whole step ---------------------------------------------------------

def forward_flops_per_token(s: dict, t: int) -> dict:
    """Operations a token of the forward pass, by part, at sequence length
    ``t`` (the attention core's share grows with it): the frozen matrices,
    the scans and convolutions, the attention cores, the tied head, and the
    low-rank pairs (``matrices``, ``conv`` and ``head`` have a frozen
    operand)."""
    n_mamba, n_attn = layers(s)
    mamba, attn, mlp = linears(s)
    inner = s["mamba_n_heads"] * s["mamba_d_head"]
    conv = inner + 2 * s["mamba_n_groups"] * s["mamba_d_state"]
    r = s["adapter_rank"]

    def frozen(pairs):
        return sum(2 * i * o for i, o in pairs)

    def low_rank(pairs):
        return sum(2 * r * (i + o) for i, o in pairs)

    return {
        "matrices": (n_mamba * frozen(mamba) + n_attn * frozen(attn)
                     + (n_mamba + n_attn) * frozen(mlp)),
        "conv": n_mamba * 2 * s["mamba_d_conv"] * conv,
        "ssm_scan": n_mamba * ssm_scan_forward(s, t)[0] / t,
        "attn_core": n_attn * attn_core_forward(s, t)[0] / t,
        "head": 2 * s["hidden_size"] * s["vocab_size"],
        "lora": (n_mamba * low_rank(mamba) + n_attn * low_rank(attn)
                 + (n_mamba + n_attn) * low_rank(mlp)),
    }


def train_flops_per_sequence(config: dict, mix: dict) -> int:
    """What ``train_flops_per_sample`` freezes: a sample is one packed
    sequence; products with a frozen operand twice their forward, the
    others three times."""
    per_token = forward_flops_per_token(shapes(config), tokens(mix))
    return int(round(tokens(mix) * sum(
        (FROZEN if part in ("matrices", "conv", "head") else TRAINED) * ops
        for part, ops in per_token.items())))


def parameters(s: dict) -> dict:
    """``{"base", "adapters"}``: the frozen parameters (tied head counted
    once) and the low-rank pairs'."""
    d, h = s["hidden_size"], s["mamba_n_heads"]
    n_mamba, n_attn = layers(s)
    mamba, attn, mlp = linears(s)
    inner = h * s["mamba_d_head"]
    conv = inner + 2 * s["mamba_n_groups"] * s["mamba_d_state"]
    r = s["adapter_rank"]

    def frozen(pairs):
        return sum(i * o for i, o in pairs)

    def low_rank(pairs):
        return sum(r * (i + o) for i, o in pairs)

    # convolution and its bias, A_log, dt_bias, D, the gated norm
    mamba_small = (s["mamba_d_conv"] + 1) * conv + 3 * h + inner
    every = frozen(mlp) + 2 * d                     # the MLP and two norms
    return {
        "base": (n_mamba * (frozen(mamba) + mamba_small + every)
                 + n_attn * (frozen(attn) + every)
                 + s["vocab_size"] * d + d),
        "adapters": (n_mamba * low_rank(mamba) + n_attn * low_rank(attn)
                     + (n_mamba + n_attn) * low_rank(mlp)),
    }


# --- a round ----------------------------------------------------------------

def steps_per_round(mix: dict) -> int:
    """Local steps a round: every sampled client's sequences, a batch at a
    time, ``epochs`` times."""
    per_client = -(-int(mix["counts"]["per_client"]) // int(mix["batch"]))
    return int(mix["cohort"]) * per_client * int(mix["epochs"])


def roofline_ms_per_round(kernel: str, config: dict, mix: dict,
                          peaks: dict) -> float:
    """The least time a round's calls of ``kernel`` could take on a chip
    with ``peaks``: the larger of its operations over the peak rate and its
    bytes over the memory's, forward and backward, over every layer that
    has it and every step of the round."""
    s, t = shapes(config), tokens(mix)
    n_mamba, n_attn = layers(s)
    forward, n_layers = {"ssm_scan": (ssm_scan_forward, n_mamba),
                         "attn_core": (attn_core_forward, n_attn)}[kernel]
    ops, moved = (int(mix["batch"]) * v for v in forward(s, t))
    seconds = max(TRAINED * ops / peaks["bf16_flops_per_s"],
                  TRAINED * moved / peaks["hbm_bytes_per_s"])
    return 1e3 * n_layers * steps_per_round(mix) * seconds
