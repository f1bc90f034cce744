"""LeNet and VGG-16 in pure JAX — the paper's own inference workloads.

Each model exposes per-layer apply functions so the UAV runtime can execute
a *placed* inference: layer j runs "on" node ``assign[j]`` (simulated), with
the intermediate activation shipped between placement units exactly as the
OULD objective prices it.  ``apply_layers(params, x, start, end)`` runs a
contiguous unit range — the execution primitive for placed inference and
for the shard_map pipeline.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from .common import dense_init


def _conv(x, w, b, stride=1, padding="SAME"):
    y = jax.lax.conv_general_dilated(
        x, w, (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + b


def _pool(x, k=2):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, k, k, 1), (1, k, k, 1), "VALID")


# ---------------------------------------------------------------------------
# LeNet — 7 placement units (matches core.profiles.lenet_profile)
# ---------------------------------------------------------------------------

def lenet_init(key, height: int = 326, width: int = 595, channels: int = 3,
               num_classes: int = 10) -> dict:
    ks = jax.random.split(key, 5)
    h, w = (height - 4) // 2, (width - 4) // 2
    h, w = (h - 4) // 2, (w - 4) // 2
    flat = h * w * 16
    return {
        "conv1": {"w": dense_init(ks[0], 75, (5, 5, channels, 6), jnp.float32),
                  "b": jnp.zeros((6,))},
        "conv2": {"w": dense_init(ks[1], 150, (5, 5, 6, 16), jnp.float32),
                  "b": jnp.zeros((16,))},
        "fc1": {"w": dense_init(ks[2], flat, (flat, 120), jnp.float32),
                "b": jnp.zeros((120,))},
        "fc2": {"w": dense_init(ks[3], 120, (120, 84), jnp.float32),
                "b": jnp.zeros((84,))},
        "fc3": {"w": dense_init(ks[4], 84, (84, num_classes), jnp.float32),
                "b": jnp.zeros((num_classes,))},
    }


def lenet_layers(params: dict) -> list[Callable]:
    return [
        lambda x: jax.nn.relu(_conv(x, params["conv1"]["w"],
                                    params["conv1"]["b"], padding="VALID")),
        lambda x: _pool(x),
        lambda x: jax.nn.relu(_conv(x, params["conv2"]["w"],
                                    params["conv2"]["b"], padding="VALID")),
        lambda x: _pool(x).reshape(x.shape[0], -1),
        lambda x: jax.nn.relu(x @ params["fc1"]["w"] + params["fc1"]["b"]),
        lambda x: jax.nn.relu(x @ params["fc2"]["w"] + params["fc2"]["b"]),
        lambda x: x @ params["fc3"]["w"] + params["fc3"]["b"],
    ]


# ---------------------------------------------------------------------------
# VGG-16 — 18 placement units (13 conv + 5 pool, head folded into unit 18)
# ---------------------------------------------------------------------------

_VGG_CFG = (64, 64, "p", 128, 128, "p", 256, 256, 256, "p",
            512, 512, 512, "p", 512, 512, 512, "p")


def vgg16_init(key, channels: int = 3, num_classes: int = 10) -> dict:
    params: dict = {}
    cin = channels
    ks = jax.random.split(key, 16)
    ki = 0
    for li, cfg in enumerate(_VGG_CFG):
        if cfg == "p":
            continue
        params[f"conv{li}"] = {
            "w": dense_init(ks[ki], 9 * cin, (3, 3, cin, cfg), jnp.float32),
            "b": jnp.zeros((cfg,))}
        cin = cfg
        ki += 1
    head_in = 7 * 7 * 512
    params["fc6"] = {"w": dense_init(ks[13], head_in, (head_in, 4096), jnp.float32),
                     "b": jnp.zeros((4096,))}
    params["fc7"] = {"w": dense_init(ks[14], 4096, (4096, 4096), jnp.float32),
                     "b": jnp.zeros((4096,))}
    params["fc8"] = {"w": dense_init(ks[15], 4096, (4096, num_classes), jnp.float32),
                     "b": jnp.zeros((num_classes,))}
    return params


def _vgg_head(params, x):
    # adaptive average pool to 7x7, then the 3 FC layers (folded unit)
    b, h, w, c = x.shape
    if h < 7 or w < 7:  # tiny test frames: zero-pad up to the pool grid
        x = jnp.pad(x, ((0, 0), (0, max(0, 7 - h)), (0, max(0, 7 - w)),
                        (0, 0)))
        h, w = max(h, 7), max(w, 7)
    hs, ws = h // 7, w // 7
    x = x[:, : hs * 7, : ws * 7]
    x = x.reshape(b, 7, hs, 7, ws, c).mean(axis=(2, 4))
    x = x.reshape(b, -1)
    x = jax.nn.relu(x @ params["fc6"]["w"] + params["fc6"]["b"])
    x = jax.nn.relu(x @ params["fc7"]["w"] + params["fc7"]["b"])
    return x @ params["fc8"]["w"] + params["fc8"]["b"]


def vgg16_layers(params: dict) -> list[Callable]:
    fns: list[Callable] = []
    for li, cfg in enumerate(_VGG_CFG):
        if cfg == "p":
            if li == len(_VGG_CFG) - 1:
                fns.append(lambda x: _vgg_head(params, _pool(x)))
            else:
                fns.append(lambda x: _pool(x))
        else:
            p = params[f"conv{li}"]
            fns.append(functools.partial(
                lambda x, p: jax.nn.relu(_conv(x, p["w"], p["b"])), p=p))
    return fns


def apply_layers(layer_fns: list[Callable], x: jax.Array,
                 start: int = 0, end: int | None = None) -> jax.Array:
    """Run units [start, end) — the placed-inference execution primitive.
    Each unit runs under ``jax.named_scope(f"unit{i}")`` (op metadata only),
    so a device trace can put its ops' time down to the unit."""
    end = end if end is not None else len(layer_fns)
    for i in range(start, end):
        with jax.named_scope(f"unit{i}"):
            x = layer_fns[i](x)
    return x
