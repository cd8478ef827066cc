"""The work of scoring one record with a DeepSeek-V2-architecture scorer
on one chip of an expert-parallel deployment.

Counted from the algorithm and the published sizes (`m`, Hugging Face
`config.json` keys), never from the compiled program: 2 FLOPs per weight
per token for every matmul of the body (MLA's projections without query
compression, the dense layers' SwiGLU, each MoE layer's router and shared
experts, and the held experts at their expected load: k · held / E
assignments a token), causal attention over the lower triangle (QK^T at
nope + rope dims, PV at v dims), and the untied head at the last position
only. Norms, softmax, the routing's top-k and the dispatch's gathers are
left out.
"""
from __future__ import annotations


def _sizes(m: dict):
    d = m["hidden_size"]
    layers = m["num_hidden_layers"]
    dense = m["first_k_dense_replace"]
    return d, layers, dense, layers - dense


def held_load(m: dict) -> float:
    """Assignments a token gives the held experts, expected under uniform
    routing: k · held / E."""
    return (m["num_experts_per_tok"] * m["n_routed_experts"]
            / m["deployment"]["published_n_routed_experts"])


def held_expert_flop_per_record(m: dict, seq_len: int) -> float:
    """The held experts' SwiGLU FLOPs for one record, at expected load."""
    d, _, _, moe = _sizes(m)
    expert = 3 * d * m["moe_intermediate_size"]
    return 2.0 * moe * held_load(m) * expert * seq_len


def held_expert_weight_bytes(m: dict, bytes_per_weight: int = 2) -> float:
    """Bytes of the held experts' weights, read once a prefill call."""
    d, _, _, moe = _sizes(m)
    return (moe * m["n_routed_experts"] * 3 * d * m["moe_intermediate_size"]
            * bytes_per_weight)


def prefill_flop_per_record(m: dict, seq_len: int) -> float:
    """Useful FLOPs to score one record of `seq_len` tokens."""
    d, layers, dense, moe = _sizes(m)
    h = m["num_attention_heads"]
    r = m["kv_lora_rank"]
    dn, dr, dv = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                  m["v_head_dim"])
    mla_w = (d * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv)
             + h * dv * d)
    dense_w = 3 * d * m["intermediate_size"]
    router_w = d * m["deployment"]["published_n_routed_experts"]
    shared_w = 3 * d * m["n_shared_experts"] * m["moe_intermediate_size"]
    body = 2.0 * seq_len * (layers * mla_w + dense * dense_w
                            + moe * (router_w + shared_w))
    attn = 2.0 * (seq_len * seq_len / 2.0) * h * (dn + dr + dv) * layers
    head = 2.0 * d * m["vocab_size"]
    return (body + held_expert_flop_per_record(m, seq_len) + attn + head)
