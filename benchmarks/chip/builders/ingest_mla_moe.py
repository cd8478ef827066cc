"""A DeepSeek-V2-architecture proxy scorer feeding an archive, on one
chip's share of an expert-parallel deployment: `configs/*.json` of kind
`ingest_mla_moe`.

The file's top-level keys are the scorer's published sizes (Hugging Face
`config.json` keys) with `n_routed_experts` the routed experts this chip
holds; `deployment` gives the router's published width and the first
held expert, `archive` the archive it appends to. The canonical weights
(`chipbench.deepseek_v2_ref.make_weights`) are re-nested into the
program's parameter tree, checked against the program's own `init`
shapes, and served by the program's jitted prefill (`jit_serve_prefill`),
as `builders/ingest.py` serves a Llama scorer.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import deepseek_v2_ref, plugins

# The published router and attention this builder maps; anything else is
# another model and is refused.
PUBLISHED = {"model_type": "deepseek_v2", "q_lora_rank": None,
             "scoring_func": "softmax", "topk_method": "greedy",
             "n_group": 1, "moe_layer_freq": 1, "hidden_act": "silu",
             "tie_word_embeddings": False, "attention_bias": False}


def model_config(m: dict):
    """The program's `ModelConfig` for the published sizes in `m`."""
    from repro.configs.base import ModelConfig

    for key, want in PUBLISHED.items():
        if m[key] != want:
            raise ValueError(f"the MLA/MoE adapter maps {key}={want!r}, "
                             f"not {m[key]!r}")
    rs = m["rope_scaling"]
    if rs["type"] != "yarn":
        raise ValueError(f"the MLA/MoE adapter maps YaRN, not {rs['type']}")
    dep = m["deployment"]
    return ModelConfig(
        name=m["name"], family="moe",
        num_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"],
        d_ff=m["intermediate_size"], vocab_size=m["vocab_size"],
        use_mla=True, q_lora_rank=0, kv_lora_rank=m["kv_lora_rank"],
        qk_nope_head_dim=m["qk_nope_head_dim"],
        qk_rope_head_dim=m["qk_rope_head_dim"], v_head_dim=m["v_head_dim"],
        rope_theta=float(m["rope_theta"]), yarn_factor=float(rs["factor"]),
        yarn_original_max_positions=rs["original_max_position_embeddings"],
        yarn_beta_fast=float(rs["beta_fast"]),
        yarn_beta_slow=float(rs["beta_slow"]),
        yarn_mscale=float(rs["mscale"]),
        yarn_mscale_all_dim=float(rs["mscale_all_dim"]),
        moe=True, num_experts=dep["published_n_routed_experts"],
        num_experts_per_tok=m["num_experts_per_tok"],
        num_shared_experts=m["n_shared_experts"],
        moe_d_ff=m["moe_intermediate_size"],
        dense_d_ff=m["intermediate_size"],
        first_k_dense=m["first_k_dense_replace"],
        norm_topk_prob=m["norm_topk_prob"],
        routed_scaling_factor=float(m["routed_scaling_factor"]),
        first_held_expert=dep["first_held_expert"],
        num_held_experts=m["n_routed_experts"],
        norm_eps=float(m["rms_norm_eps"]), dtype=m["dtype"])


def _attention(w: dict, group: str, m: dict) -> dict:
    """MLA's canonical projections as the program's `init_mla` names them:
    kv_b's per-head (nope key, value) columns split into w_uk and w_uv."""
    h, r = m["num_attention_heads"], m["kv_lora_rank"]
    dn, dv = m["qk_nope_head_dim"], m["v_head_dim"]
    kv_b = w[f"{group}.kv_b"]
    kv_b = kv_b.reshape(kv_b.shape[0], r, h, dn + dv)
    return {"wq": w[f"{group}.q_proj"], "w_dkv": w[f"{group}.kv_a"],
            "kv_norm": {"scale": w[f"{group}.kv_norm"]},
            "w_uk": kv_b[..., :dn].reshape(-1, r, h * dn),
            "w_uv": kv_b[..., dn:].reshape(-1, r, h * dv),
            "wo": w[f"{group}.o_proj"]}


def program_params(w: dict, cfg, m: dict) -> dict:
    """Re-nest the canonical weights into the program's parameter tree,
    and check the tree against the program's own `init` shapes."""
    from repro.models import model

    def norms(group):
        return {"ln1": {"scale": w[f"{group}.ln1"]},
                "ln2": {"scale": w[f"{group}.ln2"]},
                "attn": _attention(w, group, m)}

    params = {
        "embed": {"table": w["embed"]},
        "head": {"w": w["head"]},
        "ln_f": {"scale": w["ln_f"]},
        "body": {
            "dense_prefix": {**norms("dense"), "mlp": {
                "w_gate": w["dense.w_gate"], "w_up": w["dense.w_up"],
                "w_down": w["dense.w_down"]}},
            "moe_blocks": {**norms("moe"), "moe": {
                "router": w["moe.router"].astype(jnp.float32),
                "w_gate": w["moe.w_gate"], "w_up": w["moe.w_up"],
                "w_down": w["moe.w_down"],
                "shared": {"w_gate": w["moe.shared_gate"],
                           "w_up": w["moe.shared_up"],
                           "w_down": w["moe.shared_down"]}}}},
    }
    want = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), cfg))
    got = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            a.shape != b.shape or a.dtype != b.dtype for a, b in
            zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("the program's parameter tree changed: update "
                         "program_params")
    return params


def build(cfg: dict, seed: int):
    """The archive of `cfg["archive"]` and the scorer whose published
    sizes are the file's top-level keys, as `builders/ingest.py`'s
    `Ingest`."""
    from repro.launch import serve as servelib

    mcfg = model_config(cfg)          # first: a program without it fails
    arch = cfg["archive"]
    archive = plugins.load("builders", arch["kind"]).build(arch, seed)
    params = program_params(deepseek_v2_ref.make_weights(
        cfg, seed, jnp.dtype(cfg["dtype"])), mcfg, cfg)
    prefill = jax.jit(servelib.make_serve_prefill(
        mcfg, target_token=int(cfg["target_token"])))
    return plugins.load("builders", "ingest").Ingest(
        archive, cfg, mcfg, params, prefill)
