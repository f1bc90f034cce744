"""Plain reference of a CNN configuration: its weights from the seed and its
forward pass in straightforward ``jax.numpy``, read from the configuration
file's unit list.  Imports nothing of the program.

Weights are normal draws scaled by ``fan_in ** -0.5`` with zero biases, in
float32, made on the device in one jitted call and keyed by the parameter
names the configuration gives, which are the names the program's layer
builders read.  The forward pass runs any range of units at the
configuration's stated precision (``matmul_precision``, on float32
parameters and activations); ``dtype`` recomputes it in another one, which
is how the lower-precision control is made.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

_PRECISION = {"default": None, "high": jax.lax.Precision.HIGH,
              "highest": jax.lax.Precision.HIGHEST}


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed, also past 32 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed % (1 << 31)),
                              seed >> 31)


def param_shapes(config: dict) -> dict[str, tuple[tuple, int]]:
    """name -> (weight shape, fan_in) for every parametrised op."""
    h, w, c = config["frame"]
    out = {}
    for unit in config["units"]:
        for op in unit:
            kind = op["op"]
            if kind == "conv":
                k = op["k"]
                out[op["param"]] = ((k, k, c, op["cout"]), k * k * c)
                if op["pad"] == "VALID":
                    h, w = h - k + 1, w - k + 1
                c = op["cout"]
            elif kind == "maxpool":
                h, w = h // op["k"], w // op["k"]
            elif kind == "avgpool_to":
                h = w = op["size"]
            elif kind == "flatten":
                h, w, c = 1, 1, h * w * c
            elif kind == "dense":
                fi = h * w * c
                out[op["param"]] = ((fi, op["out"]), fi)
                h, w, c = 1, 1, op["out"]
    return out


def init(config: dict, seed: int) -> dict:
    """The configuration's float32 weights from ``seed``, on the device."""
    shapes = param_shapes(config)

    @jax.jit
    def make(key):
        params = {}
        for i, (name, (shape, fan_in)) in enumerate(shapes.items()):
            wk = jax.random.fold_in(key, i)
            params[name] = {
                "w": jax.random.normal(wk, shape, jnp.float32) * fan_in ** -0.5,
                "b": jnp.zeros((shape[-1],), jnp.float32)}
        return params

    return make(seed_key(seed))


def _forward(config: dict, params: dict, x: jax.Array, dtype, start: int,
             end: int) -> jax.Array:
    prec = _PRECISION[config["matmul_precision"]]
    x = x.astype(dtype)
    for unit in config["units"][start:end]:
        for op in unit:
            kind = op["op"]
            if kind == "conv":
                p = params[op["param"]]
                x = jax.lax.conv_general_dilated(
                    x, p["w"].astype(dtype), (1, 1), op["pad"],
                    dimension_numbers=("NHWC", "HWIO", "NHWC"),
                    precision=prec) + p["b"].astype(dtype)
            elif kind == "maxpool":
                k = op["k"]
                x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                          (1, k, k, 1), (1, k, k, 1), "VALID")
            elif kind == "avgpool_to":
                s = op["size"]
                b, h, w, c = x.shape
                hs, ws = h // s, w // s
                x = x[:, :hs * s, :ws * s].reshape(b, s, hs, s, ws, c)
                x = x.mean(axis=(2, 4))
            elif kind == "flatten":
                x = x.reshape(x.shape[0], -1)
            elif kind == "dense":
                p = params[op["param"]]
                x = jnp.dot(x.reshape(x.shape[0], -1), p["w"].astype(dtype),
                            precision=prec) + p["b"].astype(dtype)
            else:
                raise ValueError(f"unknown op {kind!r}")
            if op.get("relu"):
                x = jnp.maximum(x, 0)
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
