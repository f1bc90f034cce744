"""Per-architecture smoke tests: reduced config of the same family, one
forward + one train step on CPU; output shapes + finiteness; decode
continuation equals the full-sequence forward."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.models import decode_step, forward, init_params, prefill
from repro.runtime import TrainConfig, init_opt_state, make_train_step

KEY = jax.random.PRNGKey(0)


def _batch(cfg, B=2, S=32, seed=0):
    k = jax.random.PRNGKey(seed)
    if cfg.embed_stub:
        return {"embeds": jax.random.normal(k, (B, S, cfg.d_model)) * 0.3,
                "labels": jax.random.randint(k, (B, S), 0, cfg.vocab)}
    return {"tokens": jax.random.randint(k, (B, S), 0, cfg.vocab)}


@pytest.mark.parametrize("arch", C.ARCH_IDS)
def test_forward_shapes_and_finite(arch):
    cfg = C.get_config(arch).reduced()
    params = init_params(KEY, cfg)
    batch = _batch(cfg)
    logits, aux = forward(params, cfg, batch)
    assert logits.shape == (2, 32, cfg.vocab)
    assert np.isfinite(np.asarray(logits)).all()


@pytest.mark.parametrize("arch", C.ARCH_IDS)
def test_train_step_no_nans(arch):
    cfg = C.get_config(arch).reduced()
    params = init_params(KEY, cfg)
    tcfg = TrainConfig(remat=False)
    step = jax.jit(make_train_step(cfg, tcfg))
    opt = init_opt_state(params, tcfg)
    p2, o2, metrics = step(params, opt, _batch(cfg))
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    # params must actually change
    delta = sum(float(jnp.abs(a - b).sum())
                for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)))
    assert delta > 0.0


@pytest.mark.parametrize("arch", C.ARCH_IDS)
def test_decode_matches_forward(arch):
    cfg = C.get_config(arch).reduced()
    params = init_params(KEY, cfg)
    T = 24
    if cfg.embed_stub:
        emb = jax.random.normal(KEY, (1, T, cfg.d_model)) * 0.3
        full, _ = forward(params, cfg, {"embeds": emb})
        lp, _ = prefill(params, cfg, {"embeds": emb[:, :20]}, max_len=T)
        np.testing.assert_allclose(np.asarray(lp), np.asarray(full[:, 19]),
                                   rtol=3e-4, atol=3e-4)
        return
    toks = jax.random.randint(KEY, (1, T), 0, cfg.vocab)
    full, _ = forward(params, cfg, {"tokens": toks})
    lp, cache = prefill(params, cfg, {"tokens": toks[:, :20]}, max_len=T)
    np.testing.assert_allclose(np.asarray(lp), np.asarray(full[:, 19]),
                               rtol=3e-4, atol=3e-4)
    pos = jnp.int32(20)
    for i in range(20, T):
        lg, cache = decode_step(params, cfg, toks[:, i:i + 1], cache, pos)
        pos = pos + 1
        np.testing.assert_allclose(np.asarray(lg), np.asarray(full[:, i]),
                                   rtol=2e-3, atol=2e-3)


def test_swa_ring_buffer_long_context():
    """Sequence longer than the window: ring-buffer decode must equal the
    full forward (danube's long_500k mechanism at test scale)."""
    cfg = C.get_config("h2o_danube3_4b").reduced()
    import dataclasses
    cfg = dataclasses.replace(cfg, window=16)
    params = init_params(KEY, cfg)
    T = 48
    toks = jax.random.randint(KEY, (1, T), 0, cfg.vocab)
    full, _ = forward(params, cfg, {"tokens": toks})
    lp, cache = prefill(params, cfg, {"tokens": toks[:, :40]}, max_len=T)
    np.testing.assert_allclose(np.asarray(lp), np.asarray(full[:, 39]),
                               rtol=3e-4, atol=3e-4)
    pos = jnp.int32(40)
    for i in range(40, T):
        lg, cache = decode_step(params, cfg, toks[:, i:i + 1], cache, pos)
        pos = pos + 1
        np.testing.assert_allclose(np.asarray(lg), np.asarray(full[:, i]),
                                   rtol=3e-3, atol=3e-3)


def test_moe_scatter_matches_einsum_when_no_drops():
    """With generous capacity the scatter path must equal the dense path."""
    import dataclasses
    from repro.models import moe as moe_mod
    cfg = C.get_config("granite_moe_3b").reduced()
    cfg_sc = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, impl="scatter",
                                     capacity_factor=4.0))
    p = moe_mod.moe_init(KEY, cfg)
    x = jax.random.normal(KEY, (2, 16, cfg.d_model)) * 0.5
    y_e, aux_e = moe_mod.moe_apply(p, cfg, x)
    y_s, aux_s = moe_mod.moe_apply(p, cfg_sc, x)
    np.testing.assert_allclose(np.asarray(y_e), np.asarray(y_s),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(float(aux_e), float(aux_s), rtol=1e-5)


def test_cnn_lenet_vgg_forward():
    from repro.models import cnn
    x = jax.random.normal(KEY, (1, 326, 595, 3))
    lp = cnn.lenet_init(KEY)
    out = cnn.apply_layers(cnn.lenet_layers(lp), x)
    assert out.shape == (1, 10) and np.isfinite(np.asarray(out)).all()
    # VGG on a reduced frame for CPU speed
    xs = jax.random.normal(KEY, (1, 64, 64, 3))
    vp = cnn.vgg16_init(KEY)
    out = cnn.apply_layers(cnn.vgg16_layers(vp), xs)
    assert out.shape == (1, 10) and np.isfinite(np.asarray(out)).all()
    # split execution == whole execution (placement primitive)
    mid = cnn.apply_layers(cnn.vgg16_layers(vp), xs, 0, 9)
    out2 = cnn.apply_layers(cnn.vgg16_layers(vp), mid, 9)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out2), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("step", ["prefill", "decode"])
def test_full_width_traces_at_config_dtypes(step):
    """Full-width internlm2_1p8b at the config's own dtypes (f32 params,
    bf16 compute): the layer scan's carry keeps its dtype.  Shapes only."""
    cfg = C.get_config("internlm2_1p8b")
    assert (cfg.param_dtype, cfg.compute_dtype) == ("float32", "bfloat16")
    params = jax.eval_shape(lambda k: init_params(k, cfg), KEY)
    B, S, max_len = 4, 128, 145
    toks = jax.ShapeDtypeStruct((B, S), jnp.int32)
    logits, cache = jax.eval_shape(
        lambda p, t: prefill(p, cfg, {"tokens": t}, max_len=max_len),
        params, toks)
    if step == "decode":
        logits, cache = jax.eval_shape(
            lambda p, t, c, n: decode_step(p, cfg, t, c, n), params,
            jax.ShapeDtypeStruct((B, 1), jnp.int32), cache,
            jax.ShapeDtypeStruct((), jnp.int32))
    assert logits.shape == (B, cfg.vocab) and logits.dtype == jnp.float32
    assert cache[0]["k"].shape == (cfg.n_layers, B, max_len, cfg.n_kv, cfg.hd)
