"""Decode attention — Pallas TPU kernel (flash-decode style).

Single new token per sequence attending over a long KV cache: the cache is
swept in ``block_k`` VMEM tiles with online-softmax state in VMEM scratch;
queries (one vector per (batch, q-head)) stay resident.  Ring-buffer SWA
caches work unchanged — validity masking is per-slot (`len`), not
positional, matching ``ref.decode_attention``.

Memory-bound by design: the roofline term for ``decode_*`` shapes is HBM
bytes (the whole cache is read once); the kernel's job is to reach that
bound by never spilling the accumulator and streaming K/V tiles.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def decode_attention(q, k_cache, v_cache, cache_len, *, window=None,
                     scale=None, block_k=512, interpret=False):
    """q: (B,Hq,D); caches: (B,Smax,Hkv,D); cache_len: scalar/(B,) valid
    slots → (B,Hq,D)."""
    B, Hq, D = q.shape
    _, Smax, Hkv, _ = k_cache.shape
    g = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    block_k = min(block_k, max(Smax, 8))

    lens = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (B,))
    # head-major fold: q (B*Hkv, g, D); caches (B*Hkv, Smax, D)
    qf = q.reshape(B, Hkv, g, D).reshape(B * Hkv, g, D)
    kf = k_cache.transpose(0, 2, 1, 3).reshape(B * Hkv, Smax, D)
    vf = v_cache.transpose(0, 2, 1, 3).reshape(B * Hkv, Smax, D)
    lens_f = jnp.repeat(lens, Hkv)

    Smax_p = pl.cdiv(Smax, block_k) * block_k
    if Smax_p != Smax:
        kf = jnp.pad(kf, ((0, 0), (0, Smax_p - Smax), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, Smax_p - Smax), (0, 0)))

    grid = (B * Hkv, Smax_p // block_k)

    def kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref):
        ki = pl.program_id(1)
        n_valid = len_ref[pl.program_id(0)]

        @pl.when(ki == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

        qb = q_ref[0].astype(jnp.float32) * scale          # (g, d)
        kb = k_ref[0].astype(jnp.float32)                  # (bk, d)
        vb = v_ref[0].astype(jnp.float32)
        s = qb @ kb.T                                      # (g, bk)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = k_pos < jnp.minimum(n_valid, Smax)
        s = jnp.where(valid, s, _NEG_INF)

        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        acc_ref[...] = acc_ref[...] * alpha + p @ vb
        m_ref[...] = m_new
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)

        @pl.when(ki == pl.num_programs(1) - 1)
        def _final():
            o_ref[0] = (acc_ref[...]
                        / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)

    # The per-row lengths ride in SMEM by scalar prefetch: TPU blocks must
    # tile (8, 128), so a (1,)-block over the lengths vector cannot lower.
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, g, D), lambda b, j, lens: (b, 0, 0)),
                pl.BlockSpec((1, block_k, D), lambda b, j, lens: (b, j, 0)),
                pl.BlockSpec((1, block_k, D), lambda b, j, lens: (b, j, 0)),
            ],
            out_specs=pl.BlockSpec((1, g, D), lambda b, j, lens: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g, D), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B * Hkv, g, D), q.dtype),
        interpret=interpret,
    )(lens_f, qf, kf, vf)
    return out.reshape(B, Hq, D)
