"""Decoder LM assembly: pattern-grouped ``lax.scan`` over blocks.

Layers follow ``cfg.block_pattern`` repeated over depth (period p).  Blocks
are stored *stacked over groups* (G = n_layers / p) so the whole stack lowers
to one compact scan — essential for 512-device dry-run compile times — while
heterogeneous patterns (xlstm 7×mLSTM+1×sLSTM, hymba hybrid) stay exact:
the scan body executes the p pattern positions in order.

Block kinds
-----------
attn   : x + Attn(norm1(x));   x + FFN(norm2(x))     (FFN = MLP or MoE)
hybrid : x + ½(Attn + SSM)(norm1(x));  x + MLP(norm2(x))     (hymba)
mamba  : x + SSM(norm1(x))   [+ MLP if d_ff > 0]
mlstm  : x + mLSTM(norm1(x))                          (xLSTM, no FFN)
slstm  : x + sLSTM(norm1(x))
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from ..configs.base import BlockKind, ModelConfig
from ..parallel.sharding import with_dp_constraint
from . import attention as attn_mod
from . import moe as moe_mod
from . import ssm as ssm_mod
from . import xlstm as xlstm_mod
from .common import dense_init, dtype_of, mlp_apply, mlp_init, rmsnorm


# ---------------------------------------------------------------------------
# per-block init / apply / decode dispatch
# ---------------------------------------------------------------------------

def _block_init(key, cfg: ModelConfig, kind: BlockKind) -> dict:
    dt = dtype_of(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    p: dict[str, Any] = {"norm1": {"scale": jnp.ones((cfg.d_model,), dt)}}
    if kind == "attn":
        if cfg.attn == "mla":
            p["mla"] = attn_mod.mla_init(ks[0], cfg)
        else:
            p["attn"] = attn_mod.gqa_init(ks[0], cfg)
        p["norm2"] = {"scale": jnp.ones((cfg.d_model,), dt)}
        if cfg.moe is not None:
            p["moe"] = moe_mod.moe_init(ks[1], cfg)
        else:
            p["mlp"] = mlp_init(ks[1], cfg)
    elif kind == "hybrid":
        p["attn"] = attn_mod.gqa_init(ks[0], cfg)
        p["ssm"] = ssm_mod.ssm_init(ks[1], cfg)
        p["norm2"] = {"scale": jnp.ones((cfg.d_model,), dt)}
        p["mlp"] = mlp_init(ks[2], cfg)
    elif kind == "mamba":
        p["ssm"] = ssm_mod.ssm_init(ks[0], cfg)
        if cfg.d_ff > 0:
            p["norm2"] = {"scale": jnp.ones((cfg.d_model,), dt)}
            p["mlp"] = mlp_init(ks[1], cfg)
    elif kind == "mlstm":
        p["mlstm"] = xlstm_mod.mlstm_init(ks[0], cfg)
    elif kind == "slstm":
        p["slstm"] = xlstm_mod.slstm_init(ks[0], cfg)
    else:
        raise ValueError(kind)
    return p


def _block_apply(p: dict, cfg: ModelConfig, kind: BlockKind, x: jax.Array,
                 positions: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Train/prefill-without-cache path.  Returns (x, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    h = rmsnorm(x, p["norm1"]["scale"], cfg.norm_eps)
    if kind == "attn":
        if cfg.attn == "mla":
            y, _ = attn_mod.mla_apply(p["mla"], cfg, h, positions)
        else:
            y, _ = attn_mod.gqa_apply(p["attn"], cfg, h, positions)
        x = x + y
        h2 = rmsnorm(x, p["norm2"]["scale"], cfg.norm_eps)
        if cfg.moe is not None:
            y2, aux = moe_mod.moe_apply(p["moe"], cfg, h2)
        else:
            y2 = mlp_apply(p["mlp"], h2)
        x = x + y2
    elif kind == "hybrid":
        ya, _ = attn_mod.gqa_apply(p["attn"], cfg, h, positions)
        ys = ssm_mod.ssm_apply(p["ssm"], cfg, h)
        x = x + 0.5 * (ya + ys)
        x = x + mlp_apply(p["mlp"], rmsnorm(x, p["norm2"]["scale"], cfg.norm_eps))
    elif kind == "mamba":
        x = x + ssm_mod.ssm_apply(p["ssm"], cfg, h)
        if cfg.d_ff > 0:
            x = x + mlp_apply(p["mlp"], rmsnorm(x, p["norm2"]["scale"], cfg.norm_eps))
    elif kind == "mlstm":
        x = x + xlstm_mod.mlstm_apply(p["mlstm"], cfg, h)
    elif kind == "slstm":
        x = x + xlstm_mod.slstm_apply(p["slstm"], cfg, h)
    return with_dp_constraint(x), aux


def _block_decode(p: dict, cfg: ModelConfig, kind: BlockKind, x: jax.Array,
                  cache: Any, pos: jax.Array) -> tuple[jax.Array, Any]:
    """Single-token step with carried state.  Returns (x, new_cache)."""
    h = rmsnorm(x, p["norm1"]["scale"], cfg.norm_eps)
    if kind == "attn":
        if cfg.attn == "mla":
            y, cache = attn_mod.mla_decode(p["mla"], cfg, h, cache, pos)
        else:
            kv = (cache["k"], cache["v"])
            y, (k, v) = attn_mod.gqa_decode(p["attn"], cfg, h, kv, pos)
            cache = {"k": k, "v": v}
        x = x + y
        h2 = rmsnorm(x, p["norm2"]["scale"], cfg.norm_eps)
        if cfg.moe is not None:
            y2, _ = moe_mod.moe_apply(p["moe"], cfg, h2)
        else:
            y2 = mlp_apply(p["mlp"], h2)
        x = x + y2
    elif kind == "hybrid":
        kv = (cache["k"], cache["v"])
        ya, (k, v) = attn_mod.gqa_decode(p["attn"], cfg, h, kv, pos)
        ys, (cs, hs) = ssm_mod.ssm_decode(p["ssm"], cfg, h,
                                          (cache["conv"], cache["ssm"]), pos)
        cache = {"k": k, "v": v, "conv": cs, "ssm": hs}
        x = x + 0.5 * (ya + ys)
        x = x + mlp_apply(p["mlp"], rmsnorm(x, p["norm2"]["scale"], cfg.norm_eps))
    elif kind == "mamba":
        y, (cs, hs) = ssm_mod.ssm_decode(p["ssm"], cfg, h,
                                         (cache["conv"], cache["ssm"]), pos)
        cache = {"conv": cs, "ssm": hs}
        x = x + y
        if cfg.d_ff > 0:
            x = x + mlp_apply(p["mlp"], rmsnorm(x, p["norm2"]["scale"], cfg.norm_eps))
    elif kind == "mlstm":
        y, (cs, C, n, m) = xlstm_mod.mlstm_decode(
            p["mlstm"], cfg, h, (cache["conv"], cache["C"], cache["n"],
                                 cache["m"]), pos)
        cache = {"conv": cs, "C": C, "n": n, "m": m}
        x = x + y
    elif kind == "slstm":
        y, st = xlstm_mod.slstm_decode(
            p["slstm"], cfg, h, (cache["h"], cache["c"], cache["n"],
                                 cache["m"]), pos)
        cache = {"h": st[0], "c": st[1], "n": st[2], "m": st[3]}
        x = x + y
    return with_dp_constraint(x), cache


def _block_cache_shapes(cfg: ModelConfig, kind: BlockKind, batch: int,
                        seq: int, dtype) -> dict:
    if kind == "attn":
        if cfg.attn == "mla":
            return attn_mod.mla_cache_shape(cfg, batch, seq, dtype)
        k, v = attn_mod.gqa_cache_shape(cfg, batch, seq, dtype)
        return {"k": k, "v": v}
    if kind == "hybrid":
        k, v = attn_mod.gqa_cache_shape(cfg, batch, seq, dtype)
        cs, hs = ssm_mod.ssm_cache_shape(cfg, batch, dtype)
        return {"k": k, "v": v, "conv": cs, "ssm": hs}
    if kind == "mamba":
        cs, hs = ssm_mod.ssm_cache_shape(cfg, batch, dtype)
        return {"conv": cs, "ssm": hs}
    if kind == "mlstm":
        cs, C, n, m = xlstm_mod.mlstm_cache_shape(cfg, batch, dtype)
        return {"conv": cs, "C": C, "n": n, "m": m}
    if kind == "slstm":
        h, c, n, m = xlstm_mod.slstm_cache_shape(cfg, batch, dtype)
        return {"h": h, "c": c, "n": n, "m": m}
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# whole-model init / forward
# ---------------------------------------------------------------------------

def _pattern(cfg: ModelConfig) -> tuple[tuple[BlockKind, ...], int]:
    p = cfg.block_pattern
    assert cfg.n_layers % len(p) == 0, (cfg.name, cfg.n_layers, p)
    return p, cfg.n_layers // len(p)


# Dry-run probe knob (see kernels/chunked.py): unroll layer scans so XLA's
# cost model sees every group.  Never set during real execution.
UNROLL_SCANS = False


def _unroll(length: int) -> int:
    return length if UNROLL_SCANS else 1


def init_params(key, cfg: ModelConfig) -> dict:
    pat, groups = _pattern(cfg)
    dt = dtype_of(cfg.param_dtype)
    k_embed, k_head, *k_blocks = jax.random.split(key, 2 + len(pat) * groups)
    params: dict[str, Any] = {
        "embed": {"table": dense_init(k_embed, cfg.d_model,
                                      (cfg.vocab_padded, cfg.d_model), dt)},
        "final_norm": {"scale": jnp.ones((cfg.d_model,), dt)},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(k_head, cfg.d_model,
                                       (cfg.vocab_padded, cfg.d_model), dt).T
    blocks = []
    for pp, kind in enumerate(pat):
        per_group = [_block_init(k_blocks[g * len(pat) + pp], cfg, kind)
                     for g in range(groups)]
        blocks.append(jax.tree.map(lambda *xs: jnp.stack(xs), *per_group))
    params["blocks"] = blocks
    return params


def param_shapes(cfg: ModelConfig) -> Any:
    """Abstract parameter pytree (no allocation) — dry-run / checkpoints."""
    return jax.eval_shape(functools.partial(init_params, cfg=cfg),
                          jax.random.PRNGKey(0))


def _carry(cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """The residual stream between layer groups stays in the compute dtype:
    with f32 params a bf16 carry comes out of the blocks' matmuls as f32, and
    a scan carry may not change dtype."""
    return x.astype(dtype_of(cfg.compute_dtype))


def _embed_in(params, cfg: ModelConfig, batch: dict) -> jax.Array:
    if "embeds" in batch:
        x = batch["embeds"].astype(dtype_of(cfg.compute_dtype))
    else:
        x = params["embed"]["table"][batch["tokens"]]
    return x.astype(dtype_of(cfg.compute_dtype))


def _lm_logits(params, cfg: ModelConfig, x: jax.Array,
               keep_padded: bool = False) -> jax.Array:
    """Logits over the padded vocab; pad columns masked to -inf.  The padded
    form keeps the head matmul + softmax sharded on the model axis (vocab may
    not divide it unpadded); callers slice only at API boundaries."""
    x = rmsnorm(x, params["final_norm"]["scale"], cfg.norm_eps)
    head = (params["embed"]["table"].T if cfg.tie_embeddings
            else params["lm_head"])
    logits = (x @ head).astype(jnp.float32)
    if cfg.vocab_padded != cfg.vocab:
        mask = (jnp.arange(cfg.vocab_padded) >= cfg.vocab) * jnp.float32(-1e30)
        logits = logits + mask
    return logits if keep_padded else logits[..., : cfg.vocab]


def forward(params: dict, cfg: ModelConfig, batch: dict,
            remat: bool = False,
            keep_padded: bool = False) -> tuple[jax.Array, jax.Array]:
    """Full-sequence forward.  Returns (logits f32 (B,S,V), aux_loss)."""
    pat, groups = _pattern(cfg)
    x = _embed_in(params, cfg, batch)
    positions = jnp.arange(x.shape[1])

    def body(x, block_slices):
        aux = jnp.zeros((), jnp.float32)
        for pp, kind in enumerate(pat):
            x, a = _block_apply(block_slices[pp], cfg, kind, x, positions)
            aux = aux + a
        return _carry(cfg, x), aux

    if remat:
        body = jax.checkpoint(body)

    def scan_body(x, slices):
        x, aux = body(x, slices)
        return x, aux

    x, auxs = jax.lax.scan(scan_body, x, tuple(params["blocks"]),
                           unroll=_unroll(groups))
    return _lm_logits(params, cfg, x, keep_padded=keep_padded), auxs.sum()


def loss_fn(params: dict, cfg: ModelConfig, batch: dict,
            remat: bool = False) -> tuple[jax.Array, dict]:
    # padded logits keep the head matmul + softmax sharded on `model`
    from ..parallel.sharding import constrain
    logits, aux = forward(params, cfg, batch, remat=remat, keep_padded=True)
    logits = constrain(logits, ("data", None, "model"))
    labels = batch.get("labels")
    if labels is None:
        labels = batch["tokens"][:, 1:]
        logits = logits[:, :-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    loss = nll.mean() + 0.01 * aux
    return loss, {"loss": loss, "nll": nll.mean(), "aux": aux}


# ---------------------------------------------------------------------------
# serving: prefill + decode with stacked caches
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, seq: int,
               dtype=None) -> list:
    """Concrete zero-initialized cache (m-states at -30 for stability)."""
    dtype = dtype or dtype_of(cfg.compute_dtype)
    shapes = cache_shapes(cfg, batch, seq, dtype)

    def make(path, s):
        fill = -30.0 if path and path[-1] == "m" else 0.0
        return jnp.full(s.shape, fill, s.dtype)

    return _tree_map_with_key(make, shapes)


def _tree_map_with_key(fn, tree, path=()):
    if isinstance(tree, dict):
        return {k: _tree_map_with_key(fn, v, path + (k,)) for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        t = [_tree_map_with_key(fn, v, path + (i,)) for i, v in enumerate(tree)]
        return type(tree)(t) if isinstance(tree, tuple) else t
    return fn(path, tree)


def cache_shapes(cfg: ModelConfig, batch: int, seq: int, dtype=None) -> list:
    """Abstract cache pytree: list per pattern position, stacked over groups."""
    dtype = dtype or dtype_of(cfg.compute_dtype)
    pat, groups = _pattern(cfg)
    out = []
    for kind in pat:
        one = _block_cache_shapes(cfg, kind, batch, seq, dtype)
        stacked = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((groups, *s.shape), s.dtype), one)
        out.append(stacked)
    return out


def decode_step(params: dict, cfg: ModelConfig, tokens: jax.Array,
                cache: list, pos: jax.Array) -> tuple[jax.Array, list]:
    """One new token per sequence.  tokens: (B, 1) int32; pos: scalar int32
    (current cache length).  Returns (logits (B, V) f32, new cache)."""
    pat, _ = _pattern(cfg)
    x = _embed_in(params, cfg, {"tokens": tokens})

    def scan_body(x, slices):
        block_slices, cache_slices = slices
        new_caches = []
        for pp, kind in enumerate(pat):
            x, c = _block_decode(block_slices[pp], cfg, kind, x,
                                 cache_slices[pp], pos)
            new_caches.append(c)
        return _carry(cfg, x), tuple(new_caches)

    _, groups = _pattern(cfg)
    x, new_cache = jax.lax.scan(scan_body, x,
                                (tuple(params["blocks"]), tuple(cache)),
                                unroll=_unroll(groups))
    logits = _lm_logits(params, cfg, x)
    return logits[:, 0], list(new_cache)


def prefill(params: dict, cfg: ModelConfig, batch: dict,
            max_len: int | None = None) -> tuple[jax.Array, list]:
    """Prefill: full-sequence forward that also emits the serving cache,
    padded to ``max_len`` slots (decode then appends in place)."""
    pat, groups = _pattern(cfg)
    x = _embed_in(params, cfg, batch)
    S = x.shape[1]
    max_len = max_len if max_len is not None else S
    positions = jnp.arange(S)

    def pad_kv(t):
        if t.shape[1] < max_len and not (cfg.attn == "swa" and cfg.window
                                         and t.shape[1] >= cfg.window):
            smax = (min(max_len, cfg.window) if cfg.attn == "swa" and cfg.window
                    else max_len)
            t = jnp.pad(t, [(0, 0), (0, smax - t.shape[1])] +
                        [(0, 0)] * (t.ndim - 2))
        return t

    def scan_body(x, block_slices):
        caches = []
        for pp, kind in enumerate(pat):
            p = block_slices[pp]
            h = rmsnorm(x, p["norm1"]["scale"], cfg.norm_eps)
            if kind in ("attn", "hybrid") and cfg.attn != "mla":
                key = "attn"
                y, (k, v) = attn_mod.gqa_apply(p[key], cfg, h, positions)
                if cfg.attn == "swa" and cfg.window and cfg.window < S:
                    # ring-buffer layout: slot = abs_pos % window
                    k = jnp.roll(k[:, -cfg.window:], S % cfg.window, axis=1)
                    v = jnp.roll(v[:, -cfg.window:], S % cfg.window, axis=1)
                c = {"k": pad_kv(k), "v": pad_kv(v)}
                if kind == "hybrid":
                    ys, (cs, hs) = ssm_mod.ssm_prefill(p["ssm"], cfg, h)
                    y = 0.5 * (y + ys)
                    c.update({"conv": cs, "ssm": hs})
                x = x + y
                x = x + _ffn(p, cfg, x)
            elif kind == "attn":  # mla
                y, latent = attn_mod.mla_apply(p["mla"], cfg, h, positions)
                c = pad_kv(latent)
                x = x + y
                x = x + _ffn(p, cfg, x)
            elif kind == "mamba":
                y, (cs, hs) = ssm_mod.ssm_prefill(p["ssm"], cfg, h)
                c = {"conv": cs, "ssm": hs}
                x = x + y
                if cfg.d_ff > 0:
                    x = x + mlp_apply(p["mlp"], rmsnorm(x, p["norm2"]["scale"],
                                                        cfg.norm_eps))
            elif kind == "mlstm":
                y, c = xlstm_mod.mlstm_prefill(p["mlstm"], cfg, h)
                x = x + y
            elif kind == "slstm":
                y, st = xlstm_mod._slstm_core(p["slstm"], cfg, h, None)
                c = {"h": st[0], "c": st[1], "n": st[2], "m": st[3]}
                x = x + y
            x = with_dp_constraint(x)
            caches.append(c)
        return _carry(cfg, x), tuple(caches)

    x, cache = jax.lax.scan(scan_body, x, tuple(params["blocks"]),
                            unroll=_unroll(groups))
    logits = _lm_logits(params, cfg, x[:, -1:])
    return logits[:, 0], list(cache)


def _ffn(p: dict, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    h2 = rmsnorm(x, p["norm2"]["scale"], cfg.norm_eps)
    if cfg.moe is not None:
        y2, _ = moe_mod.moe_apply(p["moe"], cfg, h2)
        return y2
    return mlp_apply(p["mlp"], h2)
