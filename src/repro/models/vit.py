"""ViT-B/16 (Dosovitskiy et al., arXiv:2010.11929) as placement units.

The encoder is cut where its activation is one token sequence: unit 0 is
the patch embedding (the frame cropped to whole 16x16 patches, each patch
flattened and projected, the class token prepended, the learned position
table added), units 1..L are the pre-LN encoder blocks, and the last unit
is the head (final LayerNorm of the class token, then a linear classifier).
Every activation between units is ``(B, tokens, d)`` float32, so a stage
boundary ships one sequence.

Parameters follow the reference implementation's layout, so the widths are
read from their shapes: the patch kernel is ``(p, p, c, d)``, the query,
key and value kernels ``(d, heads, head_dim)``, the output kernel
``(heads, head_dim, d)``.  Attention goes through
:func:`repro.kernels.ops.attention` (non-causal), the Pallas flash kernel
on the TPU, under ``jax.named_scope("attention")``.

Every float32 product runs at ``Precision.HIGH`` (three bf16 passes,
~1e-5), as the flash kernel does for float32 inputs.  At one bf16 pass a
rounding change of ~1e-7 anywhere in a block (another LayerNorm formula,
another attention algorithm) grows to ~1e-3 by the block's output: each
LayerNorm rescales it and the next pass rounds afresh.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from ..kernels import ops
from .common import dense_init

LN_EPS = 1e-6     # ViT's LayerNorm epsilon
HIGH = jax.lax.Precision.HIGH


def _mm(x: jax.Array, w: jax.Array) -> jax.Array:
    return jnp.matmul(x, w, precision=HIGH)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array) -> jax.Array:
    mu = x.mean(-1, keepdims=True)
    var = jnp.square(x - mu).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * scale + bias


def embed(p: dict, x: jax.Array) -> jax.Array:
    """(B, H, W, C) frame → (B, 1 + patches, d): the top-left crop to whole
    patches, each patch flattened row by row and projected."""
    ph, pw, c, d = p["w"].shape
    b, h, w, _ = x.shape
    gh, gw = h // ph, w // pw
    x = x[:, :gh * ph, :gw * pw]
    x = x.reshape(b, gh, ph, gw, pw, c).transpose(0, 1, 3, 2, 4, 5)
    x = _mm(x.reshape(b, gh * gw, ph * pw * c), p["w"].reshape(-1, d)) + p["b"]
    cls = jnp.broadcast_to(p["cls"], (b, 1, d))
    return jnp.concatenate([cls, x], axis=1) + p["pos"]


def block(p: dict, x: jax.Array) -> jax.Array:
    """One pre-LN encoder block: x + MHA(LN(x)), then + MLP(LN(·))."""
    h = layer_norm(x, p["ln1_scale"], p["ln1_bias"])
    q, k, v = (jnp.einsum("btd,dhk->bthk", h, p[f"{n}_w"], precision=HIGH)
               + p[f"{n}_b"] for n in "qkv")
    with jax.named_scope("attention"):
        o = ops.attention(q, k, v, causal=False)
    x = x + jnp.einsum("bthk,hkd->btd", o, p["o_w"], precision=HIGH) \
        + p["o_b"]
    h = layer_norm(x, p["ln2_scale"], p["ln2_bias"])
    h = jax.nn.gelu(_mm(h, p["fc1_w"]) + p["fc1_b"], approximate=False)
    return x + _mm(h, p["fc2_w"]) + p["fc2_b"]


def head(p: dict, x: jax.Array) -> jax.Array:
    """Final LayerNorm of the class token, then the classifier:
    (B, classes)."""
    return _mm(layer_norm(x[:, 0], p["ln_scale"], p["ln_bias"]), p["w"]) \
        + p["b"]


def _blocks(params: dict) -> list[str]:
    return sorted((k for k in params if k.startswith("block")),
                  key=lambda k: int(k[len("block"):]))


def vitb16_layers(params: dict) -> list[Callable]:
    """The placement units' callables: ``embed``, one per ``block<i>`` in
    index order, ``head``; their widths come from ``params``."""
    return ([functools.partial(embed, params["embed"])]
            + [functools.partial(block, params[k]) for k in _blocks(params)]
            + [functools.partial(head, params["head"])])


def vitb16_init(key, height: int = 326, width: int = 595, channels: int = 3,
                *, patch: int = 16, d: int = 768, heads: int = 12,
                mlp: int = 3072, blocks: int = 12,
                num_classes: int = 1000) -> dict:
    """Random ViT weights in the layout :func:`vitb16_layers` reads: kernels
    normal over ``fan_in ** -0.5``, biases zero, LayerNorm scales one, the
    class token and position table normal at 0.02."""
    tokens = (height // patch) * (width // patch) + 1
    hd = d // heads
    ks = iter(jax.random.split(key, 3 + 6 * blocks + 1))
    f32 = jnp.float32

    def w(fan_in, shape):
        return dense_init(next(ks), fan_in, shape, f32)

    params = {"embed": {
        "w": w(patch * patch * channels, (patch, patch, channels, d)),
        "b": jnp.zeros((d,)),
        "cls": 0.02 * jax.random.normal(next(ks), (d,), f32),
        "pos": 0.02 * jax.random.normal(next(ks), (tokens, d), f32)}}
    for i in range(blocks):
        p = {"ln1_scale": jnp.ones((d,)), "ln1_bias": jnp.zeros((d,)),
             "ln2_scale": jnp.ones((d,)), "ln2_bias": jnp.zeros((d,))}
        for n in "qkv":
            p[f"{n}_w"] = w(d, (d, heads, hd))
            p[f"{n}_b"] = jnp.zeros((heads, hd))
        p["o_w"] = w(d, (heads, hd, d))
        p["o_b"] = jnp.zeros((d,))
        p["fc1_w"], p["fc1_b"] = w(d, (d, mlp)), jnp.zeros((mlp,))
        p["fc2_w"], p["fc2_b"] = w(mlp, (mlp, d)), jnp.zeros((d,))
        params[f"block{i}"] = p
    params["head"] = {"ln_scale": jnp.ones((d,)), "ln_bias": jnp.zeros((d,)),
                      "w": w(d, (d, num_classes)),
                      "b": jnp.zeros((num_classes,))}
    return params
