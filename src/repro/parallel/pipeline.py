"""GPipe-style pipeline executor over a ``stage`` mesh axis (shard_map +
ppermute), with stage boundaries supplied by OULD placement.

The paper's placement runs layer ranges on different nodes and ships the
boundary activation over the best link; this is the same execution shape on
a TPU mesh: stage-stacked weights live on their stage's devices, microbatch
activations flow stage→stage via ``ppermute`` (the TPU-idiomatic point-to-
point the paper's U2U transfer maps onto — DESIGN.md §2).

Schedule: standard GPipe fill/drain — T = n_micro + n_stages − 1 ticks; at
each tick every stage runs one microbatch (bubble ticks run on zeros and
their outputs are discarded by the validity mask).

Stage cuts may be **non-uniform** (:func:`pipeline_forward_stages`): each
stage's contiguous layer slice is padded to the longest stage's length and a
per-layer validity mask keeps the scan body static — padded slots re-run the
stage's last layer on a carried activation and the mask discards the result.
This is what lets OULD's real (rarely uniform) cuts run pipelined with
microbatches instead of falling back to per-request sequential execution
(DESIGN.md §5).  :func:`pipeline_forward` is the uniform special case.
"""

from __future__ import annotations

from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


def _pad_stage_slices(params_stacked, stage_sizes: Sequence[int]):
    """Re-stack a leading-L pytree into (S, P_max, ...) padded stage slices.

    Padding repeats the stage's last layer: the padded slot's output is
    discarded by the validity mask, and re-running a real layer keeps the
    dummy computation numerically tame (no zero-weight NaN paths).
    """
    p_max = max(stage_sizes)
    starts = np.concatenate([[0], np.cumsum(stage_sizes)])[:-1]

    def pad_leaf(leaf):
        parts = []
        for start, size in zip(starts, stage_sizes):
            sl = leaf[start:start + size]
            if size < p_max:
                fill = jnp.broadcast_to(sl[-1:],
                                        (p_max - size,) + sl.shape[1:])
                sl = jnp.concatenate([sl, fill])
            parts.append(sl)
        return jnp.stack(parts)

    return jax.tree.map(pad_leaf, params_stacked), p_max


def pipeline_forward_stages(block_fn: Callable, params_stacked, x, *,
                            mesh: Mesh, stage_sizes: Sequence[int],
                            stage_axis: str = "stage",
                            n_micro: int | None = None):
    """Run ``block_fn(params_slice, x_micro)`` as a pipeline with arbitrary
    contiguous stage cuts.

    params_stacked: pytree with leading dim L (layers); ``stage_sizes`` are
    the per-stage layer counts (sum L, one per mesh stage, each ≥ 1) — e.g.
    ``[s.layer_end - s.layer_start for s in plan.stages(r)]`` for an OULD
    cut.  x: (B, ...) global batch, B % n_micro == 0.  Returns the
    block-stack output, equivalent to sequentially applying all L layers
    (validated in tests against the sequential reference, uniform and not).
    """
    n_stages = mesh.shape[stage_axis]
    sizes = list(int(s) for s in stage_sizes)
    L = jax.tree.leaves(params_stacked)[0].shape[0]
    if len(sizes) != n_stages:
        raise ValueError(f"{len(sizes)} stage cuts on a {n_stages}-stage "
                         f"{stage_axis!r} mesh axis")
    if sum(sizes) != L or min(sizes) < 1:
        raise ValueError(f"stage_sizes {sizes} must partition L={L} layers "
                         "into non-empty contiguous slices")
    B = x.shape[0]
    n_micro = n_micro or n_stages
    assert B % n_micro == 0
    mb = B // n_micro

    padded, p_max = _pad_stage_slices(params_stacked, sizes)
    sizes_arr = jnp.asarray(sizes, jnp.int32)

    def stage_fn(p_local, sizes_all, x_all):
        """p_local: (1, P_max, ...) padded params slice; x_all: (B, ...) full
        batch (replicated); runs the fill/drain schedule for THIS stage."""
        sid = jax.lax.axis_index(stage_axis)
        p_local = jax.tree.map(lambda a: a[0], p_local)
        n_valid = sizes_all[sid]
        micro = x_all.reshape(n_micro, mb, *x_all.shape[1:])
        T = n_micro + n_stages - 1

        def run_block(x_in):
            def body(h, sl):
                p_slice, li = sl
                h_next = block_fn(p_slice, h)
                # padded slots carry h through unchanged (validity mask)
                return jnp.where(li < n_valid, h_next, h), None
            h, _ = jax.lax.scan(body, x_in,
                                (p_local, jnp.arange(p_max, dtype=jnp.int32)))
            return h

        def tick(carry, t):
            buf, out = carry          # buf: (mb, ...) inbound activation
            m_idx = t - sid           # microbatch this stage works on
            valid = (m_idx >= 0) & (m_idx < n_micro)
            x_in = jnp.where(
                sid == 0,
                micro[jnp.clip(m_idx, 0, n_micro - 1)],
                buf)
            y = run_block(x_in)
            # last stage banks its result; others forward downstream
            out = jax.lax.cond(
                valid & (sid == n_stages - 1),
                lambda o: o.at[jnp.clip(m_idx, 0, n_micro - 1)].set(y),
                lambda o: o, out)
            nxt = jax.lax.ppermute(
                y, stage_axis,
                [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (nxt, out), None

        out0 = jnp.zeros_like(micro)
        buf0 = jnp.zeros_like(micro[0])
        (_, out), _ = jax.lax.scan(tick, (buf0, out0), jnp.arange(T))
        # only the last stage holds real outputs; psum-broadcast them
        out = jax.lax.psum(
            jnp.where(sid == n_stages - 1, out, jnp.zeros_like(out)),
            stage_axis)
        return out.reshape(B, *x_all.shape[1:])

    fn = jax.shard_map(stage_fn, mesh=mesh,
                       in_specs=(P(stage_axis), P(), P()),
                       out_specs=P(), check_vma=False)
    return fn(padded, sizes_arr, x)


def pipeline_forward(block_fn: Callable, params_stacked, x, *, mesh: Mesh,
                     stage_axis: str = "stage", n_micro: int | None = None):
    """Uniform-cut pipeline: L % n_stages == 0, each stage runs L/S layers.
    The historical entry point — now the trivial case of
    :func:`pipeline_forward_stages`."""
    n_stages = mesh.shape[stage_axis]
    L = jax.tree.leaves(params_stacked)[0].shape[0]
    assert L % n_stages == 0
    return pipeline_forward_stages(
        block_fn, params_stacked, x, mesh=mesh,
        stage_sizes=[L // n_stages] * n_stages, stage_axis=stage_axis,
        n_micro=n_micro)
