"""Compile rehearsals for one TPU v5e chip, on a described (not attached)
topology: the main path's Pallas kernels at real widths, the f64 batched DP
sweep and one VGG16 stage.  Nothing runs; the chip's compiler accepts or
refuses each program.

The topology is described inside a fixture, never while a module is
imported: one process at a time may load the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.configs as C
from repro.core import batch_dp
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.ssm_scan import ssd_scan_pallas
from repro.models import cnn
from repro.models.ssm import _dims

V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no topology"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *specs):
    return jax.jit(fn).lower(*specs).compile()


def test_lm_kernels_compile_at_internlm2_widths(one_chip):
    cfg = C.get_config("internlm2_1p8b")
    B, S, Smax = 4, 128, 145
    hq, hkv, hd = cfg.n_heads, cfg.n_kv, cfg.hd
    bf = jnp.bfloat16
    compiled = {
        "flash_attention": _compile(
            flash_attention, _spec(one_chip, (B, S, hq, hd), bf),
            _spec(one_chip, (B, S, hkv, hd), bf),
            _spec(one_chip, (B, S, hkv, hd), bf)),
        "decode_attention": _compile(
            decode_attention, _spec(one_chip, (B, hq, hd), bf),
            _spec(one_chip, (B, Smax, hkv, hd), bf),
            _spec(one_chip, (B, Smax, hkv, hd), bf),
            _spec(one_chip, (), jnp.int32)),
        "rmsnorm": _compile(
            rmsnorm, _spec(one_chip, (B * S, cfg.d_model), bf),
            _spec(one_chip, (cfg.d_model,), bf)),
    }
    for name, c in compiled.items():
        assert "tpu_custom_call" in c.as_text(), name


def test_ssd_scan_compiles_at_hymba_widths(one_chip):
    cfg = C.get_config("hymba_1p5b")
    _, H, P, N = _dims(cfg)
    B, S = 4, 4 * cfg.ssm.chunk
    f32 = jnp.float32
    c = _compile(
        lambda x, a, b, cc, h: ssd_scan_pallas(x, a, b, cc, h,
                                               chunk=cfg.ssm.chunk),
        _spec(one_chip, (B, S, H, P), jnp.bfloat16),
        _spec(one_chip, (B, S, H), f32), _spec(one_chip, (B, S, H, N), f32),
        _spec(one_chip, (B, S, H, N), jnp.bfloat16),
        _spec(one_chip, (B, H, P, N), f32))
    assert "tpu_custom_call" in c.as_text()


def test_batched_dp_sweep_compiles_in_f64(one_chip):
    """The epoch re-solve at N=1024 (the S7 scale: LeNet's M=7, k=32)."""
    N, M, k = 1024, 7, 32
    S = batch_dp.bucket_rows(N)
    with jax.enable_x64(True):
        sweep = batch_dp._build_kernel()
        c = sweep.lower(
            _spec(one_chip, (N, N), jnp.float64),
            _spec(one_chip, (M - 1,), jnp.float64),
            _spec(one_chip, (), jnp.float64),
            _spec(one_chip, (S,), jnp.int64),
            _spec(one_chip, (S, M, k), jnp.int64),
            _spec(one_chip, (S, M, k), jnp.float64), None).compile()
    assert "f64" in c.as_text()


def test_vgg16_stage_fits_one_chip(one_chip):
    """One stage of the executed VGG16 (the engine closure's body, units
    [0, 5)) at the paper's frames, batch 4, weights passed as arguments."""
    params = jax.eval_shape(cnn.vgg16_init, jax.random.PRNGKey(0))
    params = jax.tree.map(lambda s: _spec(one_chip, s.shape, s.dtype), params)

    def stage(p, x):
        return cnn.apply_layers(cnn.vgg16_layers(p), x, 0, 5)

    c = _compile(stage, params, _spec(one_chip, (4, 326, 595, 3),
                                      jnp.float32))
    mem = c.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes)
    assert 0 < used < V5E_HBM_BYTES, used
    assert c.out_info.shape == (4, 163, 297, 128)


def test_vit_block_holds_one_flash_kernel(one_chip, monkeypatch):
    """One encoder block of the executed ViT-B/16 (the engine closure's
    body, unit [1, 2)) at 741 tokens, batch 2: its attention is the Pallas
    flash kernel, the block's only custom call."""
    from repro.kernels import ops
    from repro.models import vit
    monkeypatch.setenv("REPRO_KERNELS", "pallas")
    monkeypatch.setattr(ops, "_interpret", lambda: False)   # the chip's path
    ops._mode.cache_clear()
    params = jax.eval_shape(lambda k: vit.vitb16_init(k, blocks=1),
                            jax.random.PRNGKey(0))
    params = jax.tree.map(lambda s: _spec(one_chip, s.shape, s.dtype), params)

    def stage(p, x):
        return cnn.apply_layers(vit.vitb16_layers(p), x, 1, 2)

    try:
        c = _compile(stage, params, _spec(one_chip, (2, 741, 768),
                                          jnp.float32))
    finally:
        ops._mode.cache_clear()
    assert c.as_text().count('custom_call_target="tpu_custom_call"') == 1
    assert c.out_info.shape == (2, 741, 768)
