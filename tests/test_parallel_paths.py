"""Production-mesh parallel paths vs reference paths.

These run in a subprocess because they need a multi-device host platform
(XLA_FLAGS is locked at jax import; the main pytest process must stay
single-device for the smoke tests).
"""
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "src")
    import dataclasses
    import jax, jax.numpy as jnp
    from repro.models import attention, meshctx, moe
    from repro.configs import get_smoke_config
    from repro.launch.mesh import _make_mesh

    mesh = _make_mesh((2, 4), ("data", "model"))

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    b, s, h, kv, dh = 2, 1024, 6, 2, 64
    q = jax.random.normal(ks[0], (b, s, h, dh))
    k = jax.random.normal(ks[1], (b, s, kv, dh))
    v = jax.random.normal(ks[2], (b, s, kv, dh))
    with meshctx.mesh_context(mesh):
        o_cp = jax.jit(lambda q, k, v: attention.context_parallel_attention(
            q, k, v, m_size=4, kv_chunk=256))(q, k, v)
    o_ref = attention.chunked_causal_attention(q, k, v, q_chunk=256,
                                               kv_chunk=256)
    err = float(jnp.max(jnp.abs(o_cp - o_ref)))
    assert err < 1e-4, f"CP attention mismatch {err}"

    cfg = dataclasses.replace(get_smoke_config("deepseek-v2-236b"),
                              num_experts=8, shard_activations=True)
    p = moe.init_moe(jax.random.PRNGKey(1), cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 16, cfg.d_model)) * 0.1
    with meshctx.mesh_context(mesh):
        out_sm, aux_sm = jax.jit(lambda p, x: moe.moe_apply(p, cfg, x))(p, x)
    cfg_d = dataclasses.replace(cfg, shard_activations=False)
    out_d, aux_d = moe.moe_apply(p, cfg_d, x)
    # Dropless on both paths: each model shard's held experts plus the
    # psum give the same routed sum as one device holding all experts,
    # up to float32 summation order.
    err = float(jnp.max(jnp.abs(out_sm.astype(jnp.float32)
                                - out_d.astype(jnp.float32))))
    assert err < 1e-4, f"MoE shard_map mismatch {err}"
    assert abs(float(aux_sm["loss"]) - float(aux_d["loss"])) < 1e-3
    n_assign = x.shape[0] * x.shape[1] * cfg.num_experts_per_tok
    assert int(aux_sm["held_assignments"]) == n_assign
    assert int(aux_d["held_assignments"]) == n_assign
    print("PARALLEL_PATHS_OK")
""")


@pytest.mark.slow
def test_parallel_paths_subprocess():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=560,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert "PARALLEL_PATHS_OK" in out.stdout, out.stderr[-2000:]
