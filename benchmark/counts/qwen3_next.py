"""Operations and bytes of the Qwen3-Next configuration's layers, as functions
of the configuration file and the mix file and of nothing the program does.

A product of ``[m, k] x [k, n]`` is ``2 m k n`` operations. Training is
forward plus backward, three times the forward's operations; what a
rematerialising program computes again is not counted. Bytes are the least
traffic with the device's memory the layer needs in the step's dtype (bf16:
2 bytes), each operand read and each result written once a pass. A token's
routed experts are counted at their expectation under uniform routing:
``num_experts_per_tok x held / num_experts`` held assignments a token.

The readers under ``layer_metrics/`` divide these by a scope's device time;
``configs/qwen3_next_80b_a3b.json`` freezes ``train_flops_per_sample`` at
``train_flops_per_sequence`` (tests/test_benchmark_lm.py holds the two
together).
"""

from __future__ import annotations

BYTES = 2           # bf16 operands of the client step
TRAIN = 3           # forward + backward, in forwards


def shapes(config: dict) -> dict:
    """The model's sizes as it is run: the factory's keyword arguments."""
    return config["factory_kwargs"]


def tokens(mix: dict) -> int:
    return int(mix["sequence_length"])


def held_assignments_per_token(s: dict) -> float:
    return s["num_experts_per_tok"] * s["num_experts_held"] / s["num_experts"]


# --- the three kernels ------------------------------------------------------

def gdn_scan_forward(s: dict, t: int) -> tuple:
    """``(operations, bytes)`` of the gated delta rule over one sequence of
    ``t`` tokens in one layer, as the recurrence states it: a value head a
    token decays the state (dk dv), reads it with k (2 dk dv), writes the
    rank-one update (2 dk dv + 3 dv) and reads it with q (2 dk dv). Bytes:
    q and k of the key heads, v, the two gates, and the output."""
    hk, hv = s["linear_num_key_heads"], s["linear_num_value_heads"]
    dk, dv = s["linear_key_head_dim"], s["linear_value_head_dim"]
    ops = t * hv * (7 * dk * dv + 3 * dv)
    moved = t * (2 * hk * dk + 2 * hv * dv) * BYTES + t * 2 * hv * 4
    return ops, moved


def attn_core_forward(s: dict, t: int) -> tuple:
    """Causal softmax attention of one sequence in one layer: ``q k^T`` and
    ``p v`` over the ``t (t + 1) / 2`` visible pairs a query head. Bytes: q
    and the output of every query head, k and v of the key-value heads."""
    hq, hkv, hd = (s["num_attention_heads"], s["num_key_value_heads"],
                   s["head_dim"])
    ops = hq * 4 * hd * t * (t + 1) // 2
    moved = t * (2 * hq + 2 * hkv) * hd * BYTES
    return ops, moved


def moe_experts_forward(s: dict, t: int) -> tuple:
    """The held routed experts over one sequence in one layer: three
    ``d x f`` products an assignment. Bytes: every held expert's three
    matrices, and a row in and a row out an assignment."""
    d, f = s["hidden_size"], s["moe_intermediate_size"]
    assignments = t * held_assignments_per_token(s)
    ops = assignments * 3 * 2 * d * f
    moved = (s["num_experts_held"] * 3 * d * f + assignments * 2 * d) * BYTES
    return ops, moved


# --- the whole step ---------------------------------------------------------

def forward_flops_per_token(s: dict, t: int) -> dict:
    """Operations a token of the forward pass, by part, at sequence length
    ``t`` (the attention core's share grows with it)."""
    d = s["hidden_size"]
    hk, hv = s["linear_num_key_heads"], s["linear_num_value_heads"]
    dk, dv = s["linear_key_head_dim"], s["linear_value_head_dim"]
    hq, hkv, hd = (s["num_attention_heads"], s["num_key_value_heads"],
                   s["head_dim"])
    f, fs = s["moe_intermediate_size"], s["shared_expert_intermediate_size"]
    conv = 2 * hk * dk + hv * dv
    layers = s["num_hidden_layers"]
    full = layers // s["full_attention_interval"]
    gdn = (2 * d * (conv + hv * dv) + 2 * d * 2 * hv          # in projections
           + 2 * s["linear_conv_kernel_dim"] * conv           # convolution
           + gdn_scan_forward(s, t)[0] / t
           + 2 * hv * dv * d)                                 # out projection
    attn = (2 * d * hq * 2 * hd + 2 * 2 * d * hkv * hd
            + attn_core_forward(s, t)[0] / t + 2 * hq * hd * d)
    moe = (2 * d * s["num_experts"]                           # router
           + moe_experts_forward(s, t)[0] / t
           + 3 * 2 * d * fs + 2 * d)                          # shared + gate
    return {"gdn": (layers - full) * gdn, "attn": full * attn,
            "moe": layers * moe, "head": 2 * d * s["vocab_size"]}


def train_flops_per_sequence(config: dict, mix: dict) -> int:
    """What ``train_flops_per_sample`` freezes: a sample is one packed
    sequence."""
    t = tokens(mix)
    return int(round(
        TRAIN * t * sum(forward_flops_per_token(shapes(config), t).values())))


def parameters(s: dict) -> int:
    d = s["hidden_size"]
    hk, hv = s["linear_num_key_heads"], s["linear_num_value_heads"]
    dk, dv = s["linear_key_head_dim"], s["linear_value_head_dim"]
    hq, hkv, hd = (s["num_attention_heads"], s["num_key_value_heads"],
                   s["head_dim"])
    f, fs = s["moe_intermediate_size"], s["shared_expert_intermediate_size"]
    conv = 2 * hk * dk + hv * dv
    layers = s["num_hidden_layers"]
    full = layers // s["full_attention_interval"]
    gdn = (d * (conv + hv * dv) + d * 2 * hv
           + s["linear_conv_kernel_dim"] * conv + 2 * hv + dv + hv * dv * d)
    attn = d * hq * 2 * hd + 2 * d * hkv * hd + 2 * hd + hq * hd * d
    moe = (d * s["num_experts"] + s["num_experts_held"] * 3 * d * f
           + 3 * d * fs + d)
    return ((layers - full) * gdn + full * attn + layers * (moe + 2 * d)
            + 2 * d * s["vocab_size"] + d)


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
    full = s["num_hidden_layers"] // s["full_attention_interval"]
    forward, layers = {
        "gdn_scan": (gdn_scan_forward, s["num_hidden_layers"] - full),
        "attn_core": (attn_core_forward, full),
        "moe_experts": (moe_experts_forward, s["num_hidden_layers"]),
    }[kernel]
    batch = int(mix["batch"])
    if kernel == "moe_experts":     # one call reads the weights once
        ops, moved = forward(s, t * batch)
    else:                           # a sequence at a time
        ops, moved = (batch * v for v in forward(s, t))
    seconds = max(TRAIN * ops / peaks["bf16_flops_per_s"],
                  TRAIN * moved / peaks["hbm_bytes_per_s"])
    return 1e3 * layers * steps_per_round(mix) * seconds
