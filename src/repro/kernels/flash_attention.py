"""Flash attention — Pallas TPU kernel (online-softmax, VMEM-tiled).

TPU adaptation notes (vs. the CUDA flash-attention the literature targets):
no warps/shared-memory banking — instead we tile (Sq × Skv) into
``(block_q × block_k)`` VMEM blocks sized for the MXU (multiples of 128 on
the lane dim), keep the running max / denominator / f32 accumulator in VMEM
scratch carried across the innermost KV grid dim, and finalize the output
block on the last KV step.  Causal + sliding-window masks are applied with
block-position iotas.  GQA is handled by folding query-head groups onto
their KV head (head-major batch fold).

Oracle: ``ref.attention`` — swept over shapes/dtypes in
``tests/test_kernels_attention.py`` (interpret=True on CPU).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def flash_attention(q, k, v, *, causal=True, window=None, scale=None,
                    kv_offset=0, block_q=128, block_k=128, interpret=False):
    """q: (B,Sq,Hq,D); k,v: (B,Skv,Hkv,D) → (B,Sq,Hq,D)."""
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, _ = k.shape
    assert Hq % Hkv == 0
    g = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    block_q = min(block_q, max(Sq, 8))
    block_k = min(block_k, max(Skv, 8))
    # float32 inputs contract in three bf16 passes, as XLA's Precision.HIGH
    # does (Mosaic's default is one; its own HIGHEST takes six).  Narrower
    # inputs keep one.
    dot = _dot_bf16x3 if q.dtype == jnp.float32 else jnp.dot

    # head-major fold: (B*Hq, S, D); KV repeated per group
    qf = q.transpose(0, 2, 1, 3).reshape(B * Hq, Sq, D)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), g, axis=1).reshape(B * Hq, Skv, D)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), g, axis=1).reshape(B * Hq, Skv, D)

    Sq_p = pl.cdiv(Sq, block_q) * block_q
    Skv_p = pl.cdiv(Skv, block_k) * block_k
    if Sq_p != Sq:
        qf = jnp.pad(qf, ((0, 0), (0, Sq_p - Sq), (0, 0)))
    if Skv_p != Skv:
        kf = jnp.pad(kf, ((0, 0), (0, Skv_p - Skv), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, Skv_p - Skv), (0, 0)))

    grid = (B * Hq, Sq_p // block_q, Skv_p // block_k)

    def kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref):
        ki = pl.program_id(2)

        @pl.when(ki == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

        qb = q_ref[0].astype(jnp.float32) * scale          # (bq, d)
        kb = k_ref[0].astype(jnp.float32)                  # (bk, d)
        vb = v_ref[0].astype(jnp.float32)
        s = dot(qb, kb.T)                                  # (bq, bk)

        qi = pl.program_id(1)
        q_pos = (qi * block_q + kv_offset
                 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0))
        k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = k_pos < Skv
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[...]                                # (bq, 1)
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        acc_ref[...] = acc_ref[...] * alpha + dot(p, vb)
        m_ref[...] = m_new
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)

        @pl.when(ki == pl.num_programs(2) - 1)
        def _final():
            o_ref[0] = (acc_ref[...]
                        / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, Sq_p, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),   # acc
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # running denom
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out[:, :Sq].reshape(B, Hq, Sq, D).transpose(0, 2, 1, 3)


def _dot_bf16x3(a, b):
    """``a @ b`` of float32 operands to ~1e-5: each split into a bf16 high
    part and a bf16 rest, three one-pass products (hi·hi + hi·lo + lo·hi)
    accumulated in float32."""
    def split(x):
        hi = x.astype(jnp.bfloat16)
        return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)

    (a_hi, a_lo), (b_hi, b_lo) = split(a), split(b)

    def mm(x, y):
        return jnp.dot(x, y, preferred_element_type=jnp.float32)

    return mm(a_hi, b_hi) + mm(a_hi, b_lo) + mm(a_lo, b_hi)
