"""Bring-up guards for the chip: the main path compiled for a described
TPU v5e, the chip smoke script's CPU rehearsal, and where the compile
cache goes.

The v5e compiles need no chip: the TPU compiler compiles for a topology
that is described and not attached. The topology is described inside a
module-scoped fixture, which skips where it cannot be described; nothing
touches the TPU library while a module is imported. The persistent
compilation cache is off around these compiles, since an entry written for
a described chip cannot be read back without one.
"""
import dataclasses
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import deepseek_v2_lite, smollm_360m
from repro.data.pipeline import CHUNK_RECORDS
from repro.kernels.moe_combine.moe_combine import combine, tile_t
from repro.kernels.moe_gmm.moe_gmm import gmm
from repro.kernels.score_hist.score_hist import score_hist
from repro.kernels.threshold_select.threshold_select import (
    threshold_select_rows)
from repro.launch import compile_cache
from repro.launch import serve as servelib
from repro.models import model

ROOT = Path(__file__).resolve().parents[1]
# The engine's full chunk, and the ragged tail chunk of a 25e6-record shard.
CHUNK_LENGTHS = [CHUNK_RECORDS, 25_000_000 % CHUNK_RECORDS]
V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # no compiler logs in /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means no TPU
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was_on)
            compilation_cache.reset_cache()


def _f32(n, sharding):
    return jax.ShapeDtypeStruct((n,), jnp.float32, sharding=sharding)


@pytest.mark.parametrize("n", CHUNK_LENGTHS)
def test_score_hist_compiles_for_v5e(one_chip, n):
    compiled = score_hist.lower(_f32(n, one_chip), num_bins=4096).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= n * 4
    assert mem.output_size_in_bytes < 64 * 1024      # three 4096-bin rows


@pytest.mark.parametrize("n", CHUNK_LENGTHS)
def test_threshold_select_compiles_for_v5e(one_chip, n):
    tau = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    compiled = threshold_select_rows.lower(_f32(n, one_chip), tau).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # One int32 lane id per record, padded to whole 4096-record blocks.
    assert compiled.memory_analysis().output_size_in_bytes == \
        -(-n // 4096) * 4096 * 4


def test_smollm_prefill_compiles_for_v5e(one_chip):
    cfg = smollm_360m.CONFIG
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(lambda k: model.init(k, cfg), jax.random.PRNGKey(0)))
    batch = {"tokens": jax.ShapeDtypeStruct((64, 512), jnp.int32,
                                            sharding=one_chip)}
    compiled = jax.jit(servelib.make_serve_prefill(cfg)).lower(
        params, batch).compile()
    assert compiled.out_info.shape == (64,)            # one score a record
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < V5E_HBM_BYTES


# V2-Lite's held-expert projections at one token block of the cell:
# 16,384 tokens x 6 assignments, gate-up then down.
@pytest.mark.parametrize("k,n", [(2048, 2 * 1408), (1408, 2048)])
def test_moe_gmm_compiles_for_v5e(one_chip, k, n):
    rows = 16384 * 6
    lhs = jax.ShapeDtypeStruct((rows, k), jnp.bfloat16, sharding=one_chip)
    rhs = jax.ShapeDtypeStruct((8, k, n), jnp.bfloat16, sharding=one_chip)
    sizes = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one_chip)
    compiled = gmm.lower(lhs, rhs, sizes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.out_info.shape == (rows, n)


def test_moe_combine_compiles_for_v5e(one_chip):
    """The combine of one token block of the cell, 16,384 tokens x 6
    slots over 8 held experts' rows: its temporaries stay far below the
    (n·k, d) bf16 buffer, 402,653,184 bytes, that no combine may write."""
    n, k, d = 16384, 6, 2048
    rows = n * k
    y = jax.ShapeDtypeStruct((rows, d), jnp.bfloat16, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((n, k), jnp.int32, sharding=one_chip)
    gates = jax.ShapeDtypeStruct((n, k), jnp.float32, sharding=one_chip)
    row_token = jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one_chip)
    sizes = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one_chip)
    compiled = combine.lower(y, pos, gates, row_token, sizes,
                             tt=tile_t(n, d)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.out_info.shape == (n, d)
    assert compiled.memory_analysis().temp_size_in_bytes < rows * d * 2


def test_dsv2_lite_prefill_compiles_for_v5e(one_chip, monkeypatch):
    """The ingest cell's program: V2-Lite holding 8 of 64 experts, batch
    128 of 512 tokens, with the grouped matmul and the combine on their
    TPU paths (the platform check is steered here: without a chip the
    backend is the CPU)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(deepseek_v2_lite.CONFIG, num_held_experts=8)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(lambda k: model.init(k, cfg), jax.random.PRNGKey(0)))
    batch = {"tokens": jax.ShapeDtypeStruct((128, 512), jnp.int32,
                                            sharding=one_chip)}
    compiled = jax.jit(servelib.make_serve_prefill(cfg)).lower(
        params, batch).compile()
    assert compiled.out_info.shape == (128,)
    text = compiled.as_text()
    assert "%moe_gmm" in text and "ragged-dot" not in text
    assert "%moe_combine" in text
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < V5E_HBM_BYTES


def test_chip_smoke_tiny_runs_on_cpu(capsys, monkeypatch):
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    chip_smoke = importlib.util.module_from_spec(spec)
    monkeypatch.setitem(sys.modules, "chip_smoke", chip_smoke)
    spec.loader.exec_module(chip_smoke)
    assert chip_smoke.main(["--tiny"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["ok"] is True
    assert last["device"] == {"platform": jax.devices()[0].platform,
                              "kind": jax.devices()[0].device_kind,
                              "count": len(jax.devices())}
    assert not any("FAILED" in line for line in lines)
    assert sum(line.startswith("check ") for line in lines) >= 14


def test_chip_smoke_refuses_cpu_without_tiny():
    if jax.devices()[0].platform == "tpu":
        pytest.skip("a TPU is attached")
    out = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py")], capture_output=True,
        text=True, timeout=120, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_compile_cache_goes_to_the_env_dir(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the cache is written there and
    nothing is set in code (a child process: JAX reads the variable as it
    is imported)."""
    cache = tmp_path / "cache"
    child = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.compile_cache import enable_compile_cache\n"
        "print(enable_compile_cache())\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(cache),
           "PYTHONPATH": str(ROOT / "src")}
    out = subprocess.run([sys.executable, "-c", child], capture_output=True,
                         text=True, timeout=120, env=env, check=True)
    assert out.stdout.strip() == str(cache)
    assert any(cache.iterdir())


def test_compile_cache_defaults_into_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        where = Path(compile_cache.enable_compile_cache())
        assert jax.config.jax_compilation_cache_dir == str(where)
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    assert where == ROOT / ".jax_cache"
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().splitlines()
