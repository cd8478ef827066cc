"""A Llama-architecture proxy scorer feeding an archive:
`configs/*.json` of kind `ingest`.

The file's top-level keys are the scorer's published sizes (Hugging
Face `config.json` keys); `archive` is the archive it appends to, built
by `builders/archive.py`. Weights are made from the seed on the device
(`chipbench.deploy.make_weights`) in the served type, re-nested into the
program's parameter tree, and served by the program's jitted prefill.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from chipbench import deploy, plugins


@dataclasses.dataclass
class Ingest:
    """The archive plus the scorer that feeds it."""
    archive: object
    model: dict                 # the configuration file: published sizes
    cfg: object                 # the program's ModelConfig
    params: Optional[dict]
    prefill: Callable

    def close(self) -> None:
        self.archive.close()
        self.params = None


def model_config(m: dict):
    """The program's `ModelConfig` for the published sizes in `m`."""
    from repro.configs.base import ModelConfig

    if not m["tie_word_embeddings"]:
        raise ValueError("the scorer adapter maps tied embeddings only")
    return ModelConfig(
        name=m["name"], family="dense",
        num_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"],
        head_dim=m.get("head_dim", 0), d_ff=m["intermediate_size"],
        vocab_size=m["vocab_size"], tie_embeddings=True,
        rope_theta=float(m["rope_theta"]), norm_eps=float(m["rms_norm_eps"]),
        dtype=m["dtype"])


def program_params(w: dict, cfg) -> dict:
    """Re-nest the canonical weights into the program's parameter tree,
    and check the tree against the program's own `init` shapes."""
    from repro.models import model

    params = {
        "embed": {"table": w["embed"]},
        "body": {"blocks": {
            "ln1": {"scale": w["ln1"]}, "ln2": {"scale": w["ln2"]},
            "attn": {"wq": w["wq"], "wk": w["wk"], "wv": w["wv"],
                     "wo": w["wo"]},
            "mlp": {"w_gate": w["w_gate"], "w_up": w["w_up"],
                    "w_down": w["w_down"]}}},
        "ln_f": {"scale": w["ln_f"]},
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


def build(cfg: dict, seed: int) -> Ingest:
    """The archive of `cfg["archive"]` and the scorer whose published
    sizes are the file's top-level keys."""
    from repro.launch import serve as servelib

    arch = cfg["archive"]
    archive = plugins.load("builders", arch["kind"]).build(arch, seed)
    mcfg = model_config(cfg)
    params = program_params(
        deploy.make_weights(cfg, seed, jnp.dtype(cfg["dtype"])), mcfg)
    prefill = jax.jit(servelib.make_serve_prefill(
        mcfg, target_token=int(cfg["target_token"])))
    return Ingest(archive, cfg, mcfg, params, prefill)
