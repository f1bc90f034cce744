"""Chunked selective-scan (SSD) — Pallas TPU kernel.

One grid step processes one (batch·head, chunk) tile entirely in VMEM:
builds the chunk-local decay matrix G[t,s] = exp(cumlog a_t − cumlog a_s),
computes the intra-chunk quadratic term ((C·Bᵀ)⊙G)·X on the MXU, applies
the carried state h (inter-chunk term), and writes the updated state for
the next chunk — the sequential chunk dependency is expressed by making
the chunk index the innermost grid dim with the state in VMEM scratch
(grid iterations on TPU are sequential per core, so the carry is legal;
this is the TPU-idiomatic replacement for the CUDA kernel's cross-block
semaphore chain).

Oracle: ``ref.ssd_scan`` (sequential); the XLA path is
``chunked.ssd_scan_chunked``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def ssd_scan_pallas(x, a, b, c, h0=None, *, chunk=256, interpret=False):
    """x: (B,S,H,P); a: (B,S,H) decay ∈ (0,1); b,c: (B,S,H,N).
    Returns (y (B,S,H,P), h_final (B,H,P,N))."""
    B, S, H, P = x.shape
    N = b.shape[-1]
    dt = x.dtype
    Q = min(chunk, S)
    pad = (Q - S % Q) % Q
    if pad:
        def zf(t):
            return jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
        x, b, c = zf(x), zf(b), zf(c)
        a = jnp.pad(a, [(0, 0), (0, pad), (0, 0)], constant_values=1.0)
    Sp = S + pad
    G = Sp // Q

    # head-major fold: (B*H, S, ·)
    xf = x.transpose(0, 2, 1, 3).reshape(B * H, Sp, P)
    bf = b.transpose(0, 2, 1, 3).reshape(B * H, Sp, N)
    cf = c.transpose(0, 2, 1, 3).reshape(B * H, Sp, N)
    la = jnp.log(jnp.maximum(a.astype(jnp.float32), 1e-37))
    # (B*H, G, Q): one head's whole log-decay is one block, so the block's
    # last two dims equal the array's (TPU blocks tile (8, 128) otherwise);
    # each grid step reads its chunk's row.
    laf = la.transpose(0, 2, 1).reshape(B * H, G, Q)
    h_init = (jnp.zeros((B * H, P, N), jnp.float32) if h0 is None
              else h0.astype(jnp.float32).reshape(B * H, P, N))

    def kernel(x_ref, b_ref, c_ref, la_ref, h0_ref, y_ref, hout_ref, h_ref):
        gi = pl.program_id(1)

        @pl.when(gi == 0)
        def _init():
            h_ref[...] = h0_ref[0]

        xb = x_ref[0].astype(jnp.float32)            # (Q, P)
        bb = b_ref[0].astype(jnp.float32)            # (Q, N)
        cb = c_ref[0].astype(jnp.float32)
        la_row = la_ref[0, pl.ds(gi, 1), :]          # (1, Q)
        # Cumulative log-decay as a column (t) and a row (s), built from
        # masked 2-D reductions: Mosaic has no 1-D cumsum or transpose here.
        row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
        tri = row >= col
        cum_t = jnp.sum(jnp.where(tri, la_row, 0.0), axis=1,
                        keepdims=True)               # (Q, 1) logA_t
        la_col = jnp.sum(jnp.where(row == col, la_row, 0.0), axis=1,
                         keepdims=True)              # (Q, 1)
        cum_s = jnp.sum(jnp.where(row <= col, la_col, 0.0), axis=0,
                        keepdims=True)               # (1, Q) logA_s
        total = jnp.sum(la_row, axis=1, keepdims=True)   # (1, 1) logA_Q
        gate = jnp.where(tri, jnp.exp(cum_t - cum_s), 0.0)   # (Q, Q) t,s
        dots = cb @ bb.T                             # (Q, Q): c_t · b_s
        y = (dots * gate) @ xb                       # intra-chunk (Q, P)
        h = h_ref[...]                               # (P, N) carried state
        y = y + jnp.exp(cum_t) * (cb @ h.T)          # inter-chunk
        y_ref[0] = y.astype(y_ref.dtype)
        w = jnp.exp(total - cum_t)                   # (Q, 1)
        h_inj = xb.T @ (bb * w)                      # (P, N)
        h_ref[...] = h * jnp.exp(total) + h_inj

        @pl.when(gi == pl.num_programs(1) - 1)
        def _final():
            hout_ref[0] = h_ref[...]

    y, h_final = pl.pallas_call(
        kernel,
        grid=(B * H, G),
        in_specs=[
            pl.BlockSpec((1, Q, P), lambda i, g: (i, g, 0)),
            pl.BlockSpec((1, Q, N), lambda i, g: (i, g, 0)),
            pl.BlockSpec((1, Q, N), lambda i, g: (i, g, 0)),
            pl.BlockSpec((1, G, Q), lambda i, g: (i, 0, 0)),
            pl.BlockSpec((1, P, N), lambda i, g: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, Q, P), lambda i, g: (i, g, 0)),
            pl.BlockSpec((1, P, N), lambda i, g: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Sp, P), dt),
            jax.ShapeDtypeStruct((B * H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(xf, bf, cf, laf, h_init)
    y = y.reshape(B, H, Sp, P).transpose(0, 2, 1, 3)[:, :S]
    return y.astype(dt), h_final.reshape(B, H, P, N)
