"""The DeepSeek-V2-Lite scorer against its float32 reference at a size
the CPU holds, the expert share, routing the capacity scheme would drop,
the cell's files and work count, and the cell run end to end."""
import copy
import dataclasses
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from chipbench_testlib import (SEED, TINY_ARCHIVE, harness,  # noqa: E402
                               load_cell)

from chipbench import deepseek_v2_ref, plugins, work_mla_moe  # noqa: E402

CELL = "ingest.dsv2-lite"
SEQ = 32
# The smoke size: d 64, 4 heads, latent 16, rope 8, 16 routed experts of
# which 4 are held, top-4, 2 shared, 1 dense + 2 MoE layers; YaRN and the
# router as published.
SMOKE = {"num_hidden_layers": 3, "hidden_size": 64,
         "num_attention_heads": 4, "num_key_value_heads": 4,
         "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
         "v_head_dim": 16, "intermediate_size": 96,
         "moe_intermediate_size": 32, "n_routed_experts": 4,
         "num_experts_per_tok": 4, "vocab_size": 256}


def smoke_config(held=4, first=0):
    cfg = copy.deepcopy(load_cell(CELL).config)
    cfg.update(SMOKE, n_routed_experts=held)
    cfg["deployment"].update(published_n_routed_experts=16,
                             first_held_expert=first)
    return cfg


def builder():
    return plugins.load("builders", "ingest_mla_moe")


def program(m, dtype=jnp.float32):
    """(the program's ModelConfig, its parameters re-nested from the
    canonical weights, the canonical weights)."""
    m = {**m, "dtype": jnp.dtype(dtype).name}
    cfg = builder().model_config(m)
    w = deepseek_v2_ref.make_weights(m, SEED, dtype)
    return cfg, builder().program_params(w, cfg, m), w


def tokens(m, n=3):
    return jax.random.randint(jax.random.PRNGKey(5), (n, SEQ), 0,
                              m["vocab_size"])


def test_program_matches_the_reference_at_smoke_size():
    """(a) proxy_scores and last-position logits in float32 against the
    plain reference. Tolerance: both float32, the program's attention
    blocked with an online softmax and its experts summed in another
    order; 1e-4 on logits of size ~0.1-1, 1e-4 nats on log-scores."""
    from repro.launch import serve as servelib
    from repro.models import model

    m = smoke_config()
    cfg, params, w = program(m)
    toks = tokens(m)
    with jax.default_matmul_precision("highest"):
        got, _ = model.last_logits(params, cfg, toks)
        scores = servelib.make_serve_prefill(cfg, target_token=1)(
            params, {"tokens": toks})
    want, _ = deepseek_v2_ref.last_logits(w, toks, m)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)
    ref_logp, _ = deepseek_v2_ref.forward(w, toks, m, 1)
    np.testing.assert_allclose(np.log(np.asarray(scores)),
                               np.asarray(ref_logp), atol=1e-4)


def _moe_inputs(m, key=7):
    """One MoE layer's canonical weights (all 16 experts) and its input."""
    ks = jax.random.split(jax.random.PRNGKey(key), 8)
    d, fe, e = m["hidden_size"], m["moe_intermediate_size"], 16
    sh = 2 * fe

    def rnd(k, shape, s=0.3):
        return jax.random.normal(k, shape, jnp.float32) * s
    lw = {"router": rnd(ks[0], (d, e)), "w_gate": rnd(ks[1], (e, d, fe)),
          "w_up": rnd(ks[2], (e, d, fe)), "w_down": rnd(ks[3], (e, fe, d)),
          "shared_gate": rnd(ks[4], (d, sh)), "shared_up": rnd(ks[5], (d, sh)),
          "shared_down": rnd(ks[6], (sh, d))}
    return lw, rnd(ks[7], (2, SEQ, d), 1.0)


def _program_moe_params(lw, first, held):
    sl = slice(first, first + held)
    return {"router": lw["router"], "w_gate": lw["w_gate"][sl],
            "w_up": lw["w_up"][sl], "w_down": lw["w_down"][sl],
            "shared": {"w_gate": lw["shared_gate"], "w_up": lw["shared_up"],
                       "w_down": lw["shared_down"]}}


@pytest.mark.parametrize("norm,scaling", [(False, 1.0), (True, 2.5)])
def test_four_expert_shares_add_up_to_the_whole_layer(norm, scaling):
    """(b) The routed parts that 4 disjoint held ranges give, plus the
    shared experts counted once, are the uncut reference MoE layer, with
    V2-Lite's gates and with renormalised, scaled ones. Tolerance:
    float32 sums in another order."""
    from repro.models import layers, moe

    gates = {"norm_topk_prob": norm, "routed_scaling_factor": scaling}
    m = {**smoke_config(held=16), **gates}
    lw, x = _moe_inputs(m)
    with jax.default_matmul_precision("highest"):
        want, _ = deepseek_v2_ref.moe_layer(x, lw, m, 0)
        total = layers.mlp({"w_gate": lw["shared_gate"],
                            "w_up": lw["shared_up"],
                            "w_down": lw["shared_down"]}, x.reshape(-1, 64))
        counted = 0
        for first in (0, 4, 8, 12):
            cfg = builder().model_config(
                {**smoke_config(4, first), **gates, "dtype": "float32"})
            p = _program_moe_params(lw, first, 4)
            part, aux = moe.held_experts_ffn(
                x.reshape(-1, 64), p["router"], p["w_gate"], p["w_up"],
                p["w_down"], cfg=cfg, gate_fn="softmax", first=first)
            total = total + part
            counted += int(aux["held_assignments"])
    assert counted == x.shape[0] * SEQ * m["num_experts_per_tok"]
    np.testing.assert_allclose(np.asarray(total).reshape(x.shape),
                               np.asarray(want), atol=1e-5, rtol=1e-5)


def test_every_token_on_one_expert_is_not_dropped():
    """(c) A router that sends every token to expert 0 (and three more):
    a capacity buffer of 1.25x the mean load would drop most of them;
    the dropless layer matches the reference. Tolerance as in (b)."""
    from repro.models import moe

    m = smoke_config()
    lw, x = _moe_inputs(m, key=11)
    lw["router"] = lw["router"].at[:, 0].set(0.0).at[0, 0].set(50.0)
    x = x.at[..., 0].set(1.0)                # expert 0's logit is 50
    cfg = builder().model_config({**m, "dtype": "float32"})
    with jax.default_matmul_precision("highest"):
        got, aux = moe.moe_apply(_program_moe_params(lw, 0, 4), cfg, x)
        want, ids = deepseek_v2_ref.moe_layer(x, lw, m, 0)
    n = x.shape[0] * SEQ
    assert bool(jnp.all(ids[..., 0] == 0))
    assert int(aux["largest_held_group"]) == n
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_the_cell_files_load_at_published_widths():
    """(f) The configuration maps to the program's V2-Lite with 8 of 64
    experts held, and the useful work is 1.12-1.13e12 FLOPs a record."""
    from repro.configs import get_config

    cell = load_cell(CELL)
    m, traffic = cell.config, cell.traffic
    cfg = builder().model_config(m)
    published = get_config("deepseek-v2-lite")
    assert cfg.num_held_experts == 8 and cfg.first_held_expert == 0
    assert dataclasses.replace(cfg, name=published.name,
                               num_held_experts=0) == published
    shapes = deepseek_v2_ref.canonical_shapes(m)
    n = sum(int(np.prod(s)) for s in shapes.values())
    assert n / 1e9 == pytest.approx(3.11, abs=0.01)
    per = work_mla_moe.prefill_flop_per_record(m, int(traffic["seq_len"]))
    assert 1.12e12 <= per <= 1.13e12
    assert work_mla_moe.held_load(m) == 0.75
    held = work_mla_moe.held_expert_flop_per_record(m, 512)
    assert held == pytest.approx(26 * 0.75 * 2 * 3 * 2048 * 1408 * 512)
    assert work_mla_moe.held_expert_weight_bytes(m) == \
        26 * 8 * 3 * 2048 * 1408 * 2


def test_an_unknown_router_is_refused():
    with pytest.raises(ValueError):
        builder().model_config({**load_cell(CELL).config,
                                "topk_method": "group_limited_greedy"})


def tiny_overrides():
    m = smoke_config()
    co = {k: m[k] for k in (*SMOKE, "deployment")}
    co["archive"] = {**m["archive"], **TINY_ARCHIVE}
    return co, {"seq_len": 64, "batch": 8, "batches_per_append": 2,
                "check_records": 8}


def test_the_cell_runs_correct_at_a_tiny_size(capsys, cpu_run):
    import json

    co, to = tiny_overrides()
    rc = harness.main(["--workload", CELL, "--seed", str(SEED),
                       "--seconds", "1.5", "--trace", "0"],
                      require_chip=False, config_override=co,
                      traffic_override=to)
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and res["correct"] is True, res["checks"]
    assert set(res["metrics"]) == {"ingest_records_per_s", "setup_s"}
    assert set(res["checks"]) == {"sketch_count_mismatch",
                                  "standing_mismatch", "score_logp_gap"}


def test_control_readings_at_a_tiny_size(cpu_run):
    co, to = tiny_overrides()
    cell = load_cell(CELL)
    cell.config = {**cell.config, **co}
    cell.traffic = {**cell.traffic, **to}
    out = plugins.load("drivers", cell.traffic["kind"]).control_readings(
        cell, SEED, 1.0)
    assert out["records"] == 8
    assert out["score_logp_gap"] <= cell.config["limits"]["score_logp_gap"]
    assert out["score_logp_gap_fp8_control"] > out["score_logp_gap"]
    assert 0.0 <= out["route_flip_share_bf16"] <= 1.0
