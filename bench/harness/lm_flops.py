"""Useful work of one LM training step, counted from shapes.

A matmul of an m x k by k x n product is 2mkn FLOPs; a training step does
the forward pass and a backward pass of twice its work, so every term is
counted 6 x its multiply-adds.  Work that rematerialisation repeats is
not counted.  Causal attention counts each query against the keys at or
before it, S(S+1)/2 pairs a sequence.  The routed experts count the rows
they were given, from the program's ``moe_rows_held`` counter, so a
device holding a share of the experts is credited with its own rows only.
"""
from __future__ import annotations


def shapes(conf: dict) -> dict:
    """The widths a step's work depends on, from the configuration file."""
    H = conf["num_attention_heads"]
    return {
        "d": conf["hidden_size"], "H": H, "V": conf["vocab_size"],
        "L": conf["num_hidden_layers"],
        "dense": conf["first_k_dense_replace"],
        "d_ff": conf["intermediate_size"],
        "f": conf["moe_intermediate_size"],
        "E": conf["n_routed_experts"], "R": conf["router_experts"],
        "shared": conf["n_shared_experts"],
        "qk": conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"],
        "nope": conf["qk_nope_head_dim"], "v": conf["v_head_dim"],
        "c": conf["kv_lora_rank"], "rope": conf["qk_rope_head_dim"],
    }


def macs_per_token(conf: dict) -> dict:
    """Multiply-adds a token needs in one forward pass, by part, without
    attention's scores and the routed experts (which depend on the
    sequence and on routing)."""
    s = shapes(conf)
    d, H = s["d"], s["H"]
    mla = (d * H * s["qk"] + d * (s["c"] + s["rope"])
           + s["c"] * H * (s["nope"] + s["v"]) + H * s["v"] * d)
    moe_layers = s["L"] - s["dense"]
    return {
        "mla": s["L"] * mla,
        "dense_ffn": s["dense"] * 3 * d * s["d_ff"],
        "moe_route": moe_layers * d * s["R"],
        "moe_shared": moe_layers * 3 * d * s["shared"] * s["f"],
        "lm_head": d * s["V"],
    }


def attention_macs(conf: dict, seq_len: int) -> int:
    """Causal scores and their weighted values, one sequence, all layers."""
    s = shapes(conf)
    pairs = seq_len * (seq_len + 1) // 2
    return s["L"] * s["H"] * pairs * (s["qk"] + s["v"])


def expert_macs(conf: dict, rows_held: int) -> int:
    """The held experts' three matmuls for ``rows_held`` (token, expert)
    rows, summed over the MoE layers."""
    s = shapes(conf)
    return rows_held * 3 * s["d"] * s["f"]


def step_flops(conf: dict, batch: int, seq_len: int, rows_held: int) -> dict:
    """Useful FLOPs of one step, by part and in ``total``; ``rows_held``
    is the step's rows over all MoE layers."""
    tokens = batch * seq_len
    out = {k: 6 * tokens * v for k, v in macs_per_token(conf).items()}
    out["attention"] = 6 * batch * attention_macs(conf, seq_len)
    out["moe_experts"] = 6 * expert_macs(conf, rows_held)
    out["total"] = sum(out.values())
    return out


def expert_least_seconds(conf: dict, rows_by_layer, flops_per_s: float,
                         bytes_per_s: float) -> float:
    """Least device time of a step's grouped expert matmuls: for each MoE
    layer, its held rows through the three products (gate, in: d -> f;
    out: f -> d), each forward and its two backward products (the input's
    and the weights' gradient), each the larger of its FLOPs over the peak
    and its bytes (bf16 operands read once, result written once) over HBM
    bandwidth.  Rematerialised work is not counted."""
    s = shapes(conf)
    d, f, E = s["d"], s["f"], s["E"]
    total = 0.0
    for r in rows_by_layer:
        for k, n in ((d, f), (d, f), (f, d)):
            op = max(2.0 * r * k * n / flops_per_s,
                     2.0 * (r * k + E * k * n + r * n) / bytes_per_s)
            total += 3 * op
    return total
