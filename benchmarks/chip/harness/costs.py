"""The benchmark's own operation and byte counts of a CNN configuration.

A configuration file lists its placement units, each a list of ops
(``conv``, ``maxpool``, ``flatten``, ``avgpool_to``, ``dense``).  From the
frame shape this module derives, per unit, the paper's (m_j, c_j, K_j)
triple — the same arithmetic as ``repro.core.profiles`` (f32 activations,
2 FLOPs per MAC, a pool costing one op per window element, a uint8 frame
as the source payload) — and, per convolution, the FLOPs and the bytes it
must move, for the roofline.
"""

from __future__ import annotations

import dataclasses

F32 = 4
BF16 = 2


@dataclasses.dataclass(frozen=True)
class OpCost:
    kind: str
    flops: float          # 2 x MACs (conv, dense); window elements (pool)
    in_bytes: float       # activation read
    out_bytes: float      # activation written
    param_bytes: float    # weights and bias
    mem_bytes: float      # the profile's m contribution


@dataclasses.dataclass(frozen=True)
class Unit:
    ops: tuple[OpCost, ...]

    @property
    def memory_bytes(self) -> float:
        return sum(o.mem_bytes for o in self.ops)

    @property
    def compute_flops(self) -> float:
        return sum(o.flops for o in self.ops)

    @property
    def output_bytes(self) -> float:
        return self.ops[-1].out_bytes


def _conv(h, w, c, op):
    k, cout = op["k"], op["cout"]
    if op["pad"] == "SAME":
        ho, wo = h, w
    else:
        ho, wo = h - k + 1, w - k + 1
    params = (k * k * c + 1) * cout * F32
    out = ho * wo * cout * F32
    inp = h * w * c * F32
    cost = OpCost("conv", 2.0 * k * k * c * cout * ho * wo, inp, out, params,
                  params + out + inp)
    return cost, (ho, wo, cout)


def _pool(h, w, c, op):
    k = op["k"]
    ho, wo = h // k, w // k
    out = ho * wo * c * F32
    inp = h * w * c * F32
    return (OpCost("maxpool", 1.0 * k * k * c * ho * wo, inp, out, 0.0,
                   out + inp), (ho, wo, c))


def _avgpool_to(h, w, c, op):
    s = op["size"]
    return (OpCost("avgpool_to", 0.0, h * w * c * F32, s * s * c * F32, 0.0,
                   0.0), (s, s, c))


def _flatten(h, w, c, op):
    return OpCost("flatten", 0.0, 0.0, h * w * c * F32, 0.0, 0.0), (1, 1,
                                                                     h * w * c)


def _dense(h, w, c, op):
    fi, fo = h * w * c, op["out"]
    params = (fi + 1) * fo * F32
    return (OpCost("dense", 2.0 * fi * fo, fi * F32, fo * F32, params,
                   params + fo * F32 + fi * F32), (1, 1, fo))


_OPS = {"conv": _conv, "maxpool": _pool, "avgpool_to": _avgpool_to,
        "flatten": _flatten, "dense": _dense}


def units(config: dict) -> list[Unit]:
    """Per-unit costs of ``config`` at its frame shape."""
    h, w, c = config["frame"]
    out = []
    for unit in config["units"]:
        ops = []
        for op in unit:
            cost, (h, w, c) = _OPS[op["op"]](h, w, c, op)
            ops.append(cost)
        out.append(Unit(tuple(ops)))
    return out


def input_bytes(config: dict) -> float:
    """K_s: the source frame as captured, one byte per channel sample."""
    h, w, c = config["frame"]
    return float(h * w * c)


def frame_flops(config: dict) -> float:
    """FLOPs of one frame through every unit."""
    return range_flops(config, 0, len(config["units"]))


def range_flops(config: dict, layer_start: int, layer_end: int) -> float:
    """FLOPs of one frame through units [layer_start, layer_end)."""
    return sum(u.compute_flops
               for u in units(config)[layer_start:layer_end])


def conv_bound_s(config: dict, layer_start: int, layer_end: int, batch: int,
                 peak_flops: float, peak_bytes_s: float) -> float:
    """Least time the chip could take for the convolutions of units
    [layer_start, layer_end) on a batch: per conv, the larger of its FLOPs
    over the peak and its bytes over HBM bandwidth.  The bytes are the
    least any implementation of the stated precision moves: the bf16
    operands of the TPU's passes (activations in and out for every frame,
    weights once), two bytes an element, where the f32 tensors hold four;
    a conversion may sit in an op of its own or write the output narrow."""
    total = 0.0
    for u in units(config)[layer_start:layer_end]:
        for o in u.ops:
            if o.kind == "conv":
                moved = (batch * (o.in_bytes + o.out_bytes)
                         + o.param_bytes) * BF16 / F32
                total += max(batch * o.flops / peak_flops,
                             moved / peak_bytes_s)
    return total


def program_profile(config: dict):
    """The configuration's costs as the program's ``ModelProfile``: the
    placement instance is the benchmark's, not the program's own model."""
    from repro.core.profiles import LayerProfile, ModelProfile
    return ModelProfile(config["name"], tuple(
        LayerProfile(f"unit{j}", u.memory_bytes, u.compute_flops,
                     u.output_bytes, sum(o.param_bytes for o in u.ops))
        for j, u in enumerate(units(config))), input_bytes(config))
