"""The benchmark's own operation and byte counts of a configuration.

A configuration file lists its placement units, each a list of ops.  Each
op kind is a file of its own, ``ops/<kind>.py``, found by name: its
``cost(shape, op)`` takes the per-frame activation shape (``(h, w, c)`` for
an image, ``(tokens, d)`` for a token sequence) and returns the op's
``OpCost`` and the shape it outputs.  From the frame shape this module walks
the units and derives, per unit, the paper's (m_j, c_j, K_j) triple — the
same arithmetic as ``repro.core.profiles`` (f32 activations, 2 FLOPs per
MAC, a uint8 frame as the source payload) — and, per kernel class that the
ops name, the least time the chip could take, for the rooflines.
"""

from __future__ import annotations

import dataclasses
import functools
from pathlib import Path

from . import runtime

F32 = 4
BF16 = 2
OPS_DIR = runtime.CHIP_DIR / "ops"


@dataclasses.dataclass(frozen=True)
class OpCost:
    flops: float          # 2 x MACs (matmuls); window elements (pools)
    in_bytes: float       # activation read
    out_bytes: float      # activation written
    param_bytes: float    # weights and bias
    mem_bytes: float      # the profile's m contribution
    kernel: str | None = None   # the roofline this op counts towards


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


@functools.cache
def _op_module(path: Path):
    return runtime.load_module(path, f"bench_op_{path.stem}")


def _op_cost(kind: str):
    """The ``cost`` function of op kind ``kind``, from ``ops/<kind>.py``."""
    path = OPS_DIR / f"{kind}.py"
    if not path.is_file():
        raise ValueError(f"unknown op kind {kind!r}: no file {path.name} in "
                         f"{OPS_DIR}")
    return _op_module(path).cost


def units(config: dict) -> list[Unit]:
    """Per-unit costs of ``config`` at its frame shape."""
    shape = tuple(config["frame"])
    out = []
    for unit in config["units"]:
        ops = []
        for op in unit:
            cost, shape = _op_cost(op["op"])(shape, op)
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


def bound_s(config: dict, layer_start: int, layer_end: int, batch: int,
            peak_flops: float, peak_bytes_s: float) -> dict[str, float]:
    """Least time the chip could take for each kernel class of units
    [layer_start, layer_end) on a batch, ``{kernel: seconds}`` over the ops
    that name a kernel: per op, the larger of its FLOPs over the peak and
    its bytes over HBM bandwidth.  The bytes are the least any
    implementation of the stated precision moves: the bf16 operands of the
    TPU's passes (activations in and out for every frame, weights once),
    two bytes an element, where the f32 tensors hold four; a conversion may
    sit in an op of its own or write the output narrow."""
    total: dict[str, float] = {}
    for u in units(config)[layer_start:layer_end]:
        for o in u.ops:
            if o.kernel is not None:
                moved = (batch * (o.in_bytes + o.out_bytes)
                         + o.param_bytes) * BF16 / F32
                total[o.kernel] = total.get(o.kernel, 0.0) + max(
                    batch * o.flops / peak_flops, moved / peak_bytes_s)
    return total


def program_profile(config: dict):
    """The configuration's costs as the program's ``ModelProfile``: the
    placement instance is the benchmark's, not the program's own model."""
    from repro.core.profiles import LayerProfile, ModelProfile
    return ModelProfile(config["name"], tuple(
        LayerProfile(f"unit{j}", u.memory_bytes, u.compute_flops,
                     u.output_bytes, sum(o.param_bytes for o in u.ops))
        for j, u in enumerate(units(config))), input_bytes(config))
