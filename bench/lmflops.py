"""Operations and bytes of the Moonlight share's training, and of its two
kinds of kernels, computed from the configuration's shapes and the
program's counted rows.

Model FLOPs (``lm_train_mfu``): a matmul of an (m, a) by an (a, b) costs
2·m·a·b; a training token costs its forward pass three times (forward,
and the two products of the backward); the recomputation under remat is
left out. Per token: the latent-attention projections, the causal
attention core (on average (S + 1)/2 keys a query), the dense layer's
FFN, each MoE layer's shared experts and router, and the vocabulary
head; the held experts count the rows the program routed to them
(``expert_rows``). An evaluated token costs its forward pass once, its
held-expert rows taken at their expectation (top-k × held ÷ routed).

Kernels (the rooflines): the grouped matmuls of one MoE layer in one
training step run 12 products of R × d × ff, R the rows routed to the
held experts: 3 forward, 3 again under remat, 3 for the rows' gradients
(``gmm``) and 3 for the weights' (``tgmm``); bf16 operands, f32 results.
The splash attention kernels take the causal pairs once each:
``fwd`` Q·Kᵀ and P·V, ``dq`` Q·Kᵀ, dO·Vᵀ and dS·K, ``dkv`` Q·Kᵀ, dO·Vᵀ,
Pᵀ·dO and dSᵀ·Q; bytes are the bf16 q, k, v, o, dO read and written once.
"""
from __future__ import annotations


def _dims(c: dict) -> dict:
    H = c["num_attention_heads"]
    return {"d": c["hidden_size"], "H": H, "r": c["kv_lora_rank"],
            "qk": c["qk_nope_head_dim"] + c["qk_rope_head_dim"],
            "nope": c["qk_nope_head_dim"], "rope": c["qk_rope_head_dim"],
            "v": c["v_head_dim"], "ff": c["moe_intermediate_size"],
            "dense_ff": c["intermediate_size"],
            "shared_ff": c["moe_intermediate_size"] * c["n_shared_experts"],
            "E": c["router_experts"], "held": c["n_routed_experts"],
            "k": c["num_experts_per_tok"], "V": c["vocab_size"],
            "dense": c["first_k_dense_replace"],
            "moe": c["num_hidden_layers"] - c["first_k_dense_replace"]}


def forward_flops(c: dict, seqs: int, seq_len: int, expert_rows) -> float:
    """Forward operations of ``seqs`` sequences; ``expert_rows`` is the
    rows routed to held experts over all MoE layers (None: expected)."""
    m = _dims(c)
    d, S, T = m["d"], seq_len, seqs * seq_len
    layers = m["dense"] + m["moe"]
    proj = (d * m["H"] * m["qk"] + d * (m["r"] + m["rope"])
            + m["r"] * m["H"] * (m["nope"] + m["v"]) + m["H"] * m["v"] * d)
    core = m["H"] * (m["qk"] + m["v"]) * (S + 1) / 2
    per_token = (layers * (proj + core)
                 + m["dense"] * 3 * d * m["dense_ff"]
                 + m["moe"] * (3 * d * m["shared_ff"] + d * m["E"])
                 + d * m["V"])
    if expert_rows is None:
        expert_rows = T * m["moe"] * m["k"] * m["held"] / m["E"]
    return 2.0 * (T * per_token + expert_rows * 3 * d * m["ff"])


def train_flops(c: dict, seqs: int, seq_len: int, expert_rows) -> float:
    return 3.0 * forward_flops(c, seqs, seq_len, expert_rows)


def expert_gmm(c: dict, rows: float, layer_steps: float,
               eval_rows: float, eval_layers: float) -> dict:
    """The grouped matmuls' operations and bytes: ``rows`` training rows
    over ``layer_steps`` (MoE layer, training step) pairs, and
    ``eval_rows`` forward rows over ``eval_layers`` MoE layer passes."""
    m = _dims(c)
    d, ff = m["d"], m["ff"]
    W = m["held"] * d * ff
    ops = 2.0 * d * ff * (12 * rows + 3 * eval_rows)
    # weights: 3 reads forward, 3 under remat, 3 for the rows' gradients
    # (bf16) and 3 f32 weight gradients; rows as in the module docstring
    train_bytes = (30 * W * layer_steps + rows * (32 * d + 34 * ff))
    eval_bytes = 6 * W * eval_layers + eval_rows * (8 * d + 10 * ff)
    return {"ops": ops, "bytes": train_bytes + eval_bytes}


def mla_attention(c: dict, kind: str, batch: int, seq_len: int) -> dict:
    """One splash-attention call over ``batch`` sequences of all heads:
    ``kind`` is ``fwd``, ``dq`` or ``dkv``."""
    m = _dims(c)
    qk, v = m["qk"], m["v"]
    pairs = batch * m["H"] * seq_len * (seq_len + 1) / 2
    width = {"fwd": qk + v, "dq": 2 * qk + v, "dkv": 2 * qk + 2 * v}[kind]
    io = {"fwd": 2 * qk + 2 * v, "dq": 3 * qk + 2 * v,
          "dkv": 4 * qk + 3 * v}[kind]
    return {"ops": 2.0 * pairs * width,
            "bytes": 2.0 * batch * m["H"] * seq_len * io}
