"""Plain reference of a ViT configuration: its weights from the seed and its
forward pass in straightforward ``jax.numpy``, read from the configuration
file's unit list.  Imports nothing of the program.

Weights are keyed by the parameter names the configuration gives, which
are the names the program's layer builder reads, in the layout of the
paper's reference implementation (patch kernel ``(p, p, c, d)``; query, key
and value kernels ``(d, heads, head_dim)``; output kernel ``(heads,
head_dim, d)``), drawn in float32 on the device in one jitted call.  The
forward pass runs any range of units at the configuration's stated
precision (``matmul_precision``, for every product): on the frame for
unit 0, on the ``(B, tokens, d)`` sequence after it.  ``dtype`` recomputes
it in another precision, which is how the lower-precision control is made.
Attention is written out: scores, a softmax over every token, the
weighted sum.

Departures from arXiv:2010.11929, all stated under the configuration's
``assumed``: the frame is the drone frame cropped to whole patches (the
paper's 224x224 gives 197 tokens); the position table is drawn at the
cropped grid's size instead of interpolated from a pretrained one; the head
is the single linear layer of fine-tuning, not the pre-training MLP; the
weights are random draws, not trained ones.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

_PRECISION = {"default": None, "high": jax.lax.Precision.HIGH,
              "highest": jax.lax.Precision.HIGHEST}


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed, also past 32 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed % (1 << 31)),
                              seed >> 31)


def param_shapes(config: dict) -> dict[str, dict[str, tuple[tuple, object]]]:
    """group -> name -> (shape, init) for every parametrised op; ``init``
    is ``"zeros"``, ``"ones"`` or the scale of a normal draw
    (``fan_in ** -0.5``; 0.02 for the class token and position table)."""
    h, w, c = config["frame"]
    out: dict[str, dict] = {}
    d = None
    for unit in config["units"]:
        for op in unit:
            kind = op["op"]
            g = out.setdefault(op.get("param", ""), {})
            if kind == "patch_embed":
                p, d = op["patch"], op["d"]
                t = (h // p) * (w // p) + 1
                g.update(w=((p, p, c, d), (p * p * c) ** -0.5),
                         b=((d,), "zeros"), cls=((d,), 0.02),
                         pos=((t, d), 0.02))
            elif kind == "vit_qkv":
                heads = op["heads"]
                g.update(ln1_scale=((d,), "ones"), ln1_bias=((d,), "zeros"))
                for n in "qkv":
                    g[f"{n}_w"] = ((d, heads, d // heads), d ** -0.5)
                    g[f"{n}_b"] = ((heads, d // heads), "zeros")
            elif kind == "vit_proj_mlp":
                f = op["mlp"]
                g.update(o_w=((heads, d // heads, d), d ** -0.5),
                         o_b=((d,), "zeros"),
                         ln2_scale=((d,), "ones"), ln2_bias=((d,), "zeros"),
                         fc1_w=((d, f), d ** -0.5), fc1_b=((f,), "zeros"),
                         fc2_w=((f, d), f ** -0.5), fc2_b=((d,), "zeros"))
            elif kind == "vit_head":
                fo = op["out"]
                g.update(ln_scale=((d,), "ones"), ln_bias=((d,), "zeros"),
                         w=((d, fo), d ** -0.5), b=((fo,), "zeros"))
            elif kind != "attention":
                raise ValueError(f"unknown op {kind!r}")
    out.pop("", None)
    return out


def init(config: dict, seed: int) -> dict:
    """The configuration's float32 weights from ``seed``, on the device."""
    shapes = param_shapes(config)

    @jax.jit
    def make(key):
        params, i = {}, 0
        for group, names in shapes.items():
            params[group] = {}
            for name, (shape, how) in names.items():
                if how == "zeros":
                    v = jnp.zeros(shape, jnp.float32)
                elif how == "ones":
                    v = jnp.ones(shape, jnp.float32)
                else:
                    v = jax.random.normal(jax.random.fold_in(key, i), shape,
                                          jnp.float32) * how
                    i += 1
                params[group][name] = v
        return params

    return make(seed_key(seed))


def _layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def _forward(config: dict, params: dict, x: jax.Array, dtype, start: int,
             end: int) -> jax.Array:
    prec = _PRECISION[config["matmul_precision"]]
    eps = config["layer_norm_eps"]
    x = x.astype(dtype)

    def p(op, name):
        return params[op["param"]][name].astype(dtype)

    for unit in config["units"][start:end]:
        for op in unit:
            kind = op["op"]
            if kind == "patch_embed":
                k = op["patch"]
                b, h, w, _ = x.shape
                x = x[:, :h // k * k, :w // k * k]
                x = jax.lax.conv_general_dilated(
                    x, p(op, "w"), (k, k), "VALID",
                    dimension_numbers=("NHWC", "HWIO", "NHWC"),
                    precision=prec) + p(op, "b")
                x = x.reshape(b, -1, x.shape[-1])
                cls = jnp.broadcast_to(p(op, "cls"), (b, 1, x.shape[-1]))
                x = jnp.concatenate([cls, x], axis=1) + p(op, "pos")
            elif kind == "vit_qkv":
                resid = x
                y = _layer_norm(x, p(op, "ln1_scale"), p(op, "ln1_bias"), eps)
                q, k, v = (jnp.einsum("btd,dhe->bthe", y, p(op, f"{n}_w"),
                                      precision=prec) + p(op, f"{n}_b")
                           for n in "qkv")
            elif kind == "attention":
                scores = jnp.einsum("bqhe,bkhe->bhqk", q, k, precision=prec)
                scores = scores / math.sqrt(q.shape[-1])
                weights = jax.nn.softmax(scores, axis=-1)
                o = jnp.einsum("bhqk,bkhe->bqhe", weights, v, precision=prec)
            elif kind == "vit_proj_mlp":
                x = resid + jnp.einsum("bqhe,hed->bqd", o, p(op, "o_w"),
                                       precision=prec) + p(op, "o_b")
                y = _layer_norm(x, p(op, "ln2_scale"), p(op, "ln2_bias"), eps)
                y = jnp.dot(y, p(op, "fc1_w"), precision=prec) + p(op, "fc1_b")
                y = 0.5 * y * (1.0 + jax.lax.erf(y / math.sqrt(2.0)))
                x = x + jnp.dot(y, p(op, "fc2_w"), precision=prec) \
                    + p(op, "fc2_b")
            elif kind == "vit_head":
                y = _layer_norm(x[:, 0], p(op, "ln_scale"), p(op, "ln_bias"),
                                eps)
                x = jnp.dot(y, p(op, "w"), precision=prec) + p(op, "b")
            else:
                raise ValueError(f"unknown op {kind!r}")
    return x.astype(jnp.float32)


@functools.lru_cache(maxsize=None)
def _jitted(config_key: str, dtype_name: str, start: int, end: int):
    config = json.loads(config_key)
    dtype = jnp.dtype(dtype_name)
    return jax.jit(lambda params, x: _forward(config, params, x, dtype,
                                              start, end))


def forward(config: dict, params: dict, x, dtype: str = "float32",
            start: int = 0, end: int | None = None) -> np.ndarray:
    """Units [start, end) of the configuration (all by default) on a block
    ``x`` of inputs to unit ``start``, on the host."""
    end = len(config["units"]) if end is None else end
    fn = _jitted(json.dumps(config, sort_keys=True), dtype, start, end)
    return np.asarray(fn(params, jnp.asarray(x)))
