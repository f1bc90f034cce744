"""Mixture-of-Experts FFN with top-k routing.

Two implementations behind ``cfg.moe.impl``:

* ``scatter`` (production): sort-based grouped matmul.  Tokens are argsorted
  by expert id, packed into per-expert capacity buffers with a scatter-add,
  batched through the expert SwiGLU with ``ecd,edf->ecf`` einsums (E on the
  ``model`` mesh axis = expert parallelism), and combined back with the gate
  weights.  Compute is O(tokens·top_k·capacity_factor) — FLOPs-honest for the
  roofline (a dense O(E) formulation would inflate HLO_FLOPs ~E/top_k×).
  Over-capacity tokens are dropped (standard Switch semantics).

* ``einsum`` (tiny configs / ablation): dense "run every expert on every
  token, mask by gate" — exact top-k semantics, no drops, O(E) compute.
  Used by smoke tests (exactness) and as a perf-pass ablation.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .common import dense_init, dtype_of


def moe_init(key, cfg: ModelConfig) -> dict:
    m = cfg.moe
    assert m is not None
    d, f, e = cfg.d_model, cfg.d_ff, m.num_experts
    dt = dtype_of(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    return {
        "router": dense_init(ks[0], d, (d, e), jnp.float32),
        "w_in": dense_init(ks[1], d, (e, d, f), dt),
        "w_gate": dense_init(ks[2], d, (e, d, f), dt),
        "w_out": dense_init(ks[3], f, (e, f, d), dt),
    }


def _route(p: dict, cfg: ModelConfig, x2: jax.Array):
    """x2: (T, d) → gates (T, K) softmax-normalized over chosen experts,
    idx (T, K) int32, plus the router aux loss (load balancing)."""
    m = cfg.moe
    logits = (x2.astype(jnp.float32) @ p["router"])          # (T, E)
    topv, topi = jax.lax.top_k(logits, m.top_k)
    gates = jax.nn.softmax(topv, axis=-1)
    # Switch-style load-balance aux loss: E · Σ_e f_e · p_e
    probs = jax.nn.softmax(logits, axis=-1)
    onehot = jax.nn.one_hot(topi[:, 0], m.num_experts, dtype=jnp.float32)
    aux = m.num_experts * jnp.mean(probs.mean(0) * onehot.mean(0))
    return gates, topi, aux


def _expert_ffn(p: dict, xe: jax.Array) -> jax.Array:
    """xe: (E, C, d) → (E, C, d) SwiGLU per expert."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, p["w_gate"])) * \
        jnp.einsum("ecd,edf->ecf", xe, p["w_in"])
    return jnp.einsum("ecf,efd->ecd", h, p["w_out"])


def _moe_scatter(p: dict, cfg: ModelConfig, x2: jax.Array):
    m = cfg.moe
    T, d = x2.shape
    E, K = m.num_experts, m.top_k
    gates, topi, aux = _route(p, cfg, x2)
    cap = max(1, int(T * K * m.capacity_factor / E))

    flat_e = topi.reshape(T * K)                       # expert of each slot
    flat_g = gates.reshape(T * K)
    order = jnp.argsort(flat_e, stable=True)           # group slots by expert
    sorted_e = flat_e[order]
    sorted_t = order // K                              # source token of slot
    # position of each slot within its expert queue
    counts = jnp.bincount(flat_e, length=E)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(T * K) - starts[sorted_e]
    keep = pos < cap
    pos_c = jnp.minimum(pos, cap - 1)

    from ..parallel.sharding import active_mesh, constrain
    xe = jnp.zeros((E, cap, d), x2.dtype)
    src = x2[sorted_t] * keep[:, None].astype(x2.dtype)
    xe = xe.at[sorted_e, pos_c].add(src, mode="drop")
    # Expert-parallel when E divides the model axis; otherwise shard the
    # capacity dim over (data × model) so expert compute never replicates
    # (e.g. granite's E=40 on a 16-way model axis).
    mesh, axes = active_mesh()
    ep_ok = mesh is not None and E % mesh.shape[axes.model] == 0
    buf_spec = ("model", "data", None) if ep_ok else (None, "data_model", None)
    xe = constrain(xe, buf_spec)
    ye = _expert_ffn(p, xe)                            # (E, cap, d)
    ye = constrain(ye, buf_spec)
    out_slot = ye[sorted_e, pos_c] * (flat_g[order] * keep)[:, None].astype(x2.dtype)
    y = jnp.zeros_like(x2).at[sorted_t].add(out_slot, mode="drop")
    return y, aux


def _moe_einsum(p: dict, cfg: ModelConfig, x2: jax.Array):
    m = cfg.moe
    gates, topi, aux = _route(p, cfg, x2)
    # combine (T, E): summed gate per expert (handles duplicate picks)
    comb = jnp.zeros((x2.shape[0], m.num_experts), jnp.float32)
    comb = comb.at[jnp.arange(x2.shape[0])[:, None], topi].add(gates)
    ye = _expert_ffn(p, jnp.broadcast_to(x2[None], (m.num_experts, *x2.shape)))
    y = jnp.einsum("te,etd->td", comb.astype(x2.dtype), ye)
    return y, aux


# ---------------------------------------------------------------------------
# shard_map expert-parallel path (§Perf iteration — explicit collectives).
#
# GSPMD lowers the scatter into the model-sharded (E, C, d) buffer as
# full-buffer cross-replica reductions (measured ~60× the minimum traffic on
# llama4).  The explicit formulation exploits that tokens are replicated
# across the `model` axis under DP×TP: each model column packs ONLY its own
# experts' tokens locally (no dispatch communication at all), runs its expert
# shard, and one psum over `model` combines the outputs — the minimum
# possible: one (T_loc, d) all-reduce per MoE layer.
# ---------------------------------------------------------------------------

SHARD_MAP_MIN_TOKENS = 16_384  # below this, GSPMD token-movement wins


def _moe_shard_map(p: dict, cfg: ModelConfig, x2: jax.Array, mesh, axes):
    from jax.sharding import PartitionSpec as P

    m = cfg.moe
    E, K = m.num_experts, m.top_k
    msize = mesh.shape[axes.model]
    # Pad the expert dim up to the TP axis (e.g. granite's 40 → 48): dead
    # experts hold zero weights and never win routing; the ~E_pad/E extra
    # matmul work is far cheaper than GSPMD's buffer reductions (§Perf).
    E_pad = (E + msize - 1) // msize * msize
    epp = E_pad // msize
    dp = axes.dp if len(axes.dp) > 1 else axes.dp[0]
    dsize = 1
    for a in axes.dp:
        dsize *= mesh.shape[a]
    T, d = x2.shape
    t_loc = T // dsize
    cap = max(1, int(t_loc * K * m.capacity_factor / E))
    if E_pad != E:
        def padw(w):
            return jnp.pad(w, ((0, E_pad - E), (0, 0), (0, 0)))
        p = {**p, "w_gate": padw(p["w_gate"]), "w_in": padw(p["w_in"]),
             "w_out": padw(p["w_out"])}

    def local(router, w_gate, w_in, w_out, x_loc):
        col = jax.lax.axis_index(axes.model)
        gates, topi, aux = _route({"router": router}, cfg, x_loc)
        flat_e = topi.reshape(t_loc * K)
        flat_g = gates.reshape(t_loc * K)
        order = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[order]
        sorted_t = order // K
        counts = jnp.bincount(flat_e, length=E)
        starts = jnp.cumsum(counts) - counts
        pos = jnp.arange(t_loc * K) - starts[sorted_e]
        mine = (sorted_e // epp) == col
        keep = (pos < cap) & mine
        e_loc = jnp.where(mine, sorted_e - col * epp, 0)
        pos_c = jnp.minimum(pos, cap - 1)

        # FSDP gather of this column's expert shard
        wg = jax.lax.all_gather(w_gate, axes.dp, axis=1, tiled=True)
        wi = jax.lax.all_gather(w_in, axes.dp, axis=1, tiled=True)
        wo = jax.lax.all_gather(w_out, axes.dp, axis=2, tiled=True)

        xe = jnp.zeros((epp, cap, d), x_loc.dtype)
        src = x_loc[sorted_t] * keep[:, None].astype(x_loc.dtype)
        xe = xe.at[e_loc, pos_c].add(src, mode="drop")
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, wg)) * \
            jnp.einsum("ecd,edf->ecf", xe, wi)
        ye = jnp.einsum("ecf,efd->ecd", h, wo)
        out_slot = ye[e_loc, pos_c] * (flat_g[order] * keep)[:, None].astype(
            x_loc.dtype)
        y_partial = jnp.zeros_like(x_loc).at[sorted_t].add(out_slot,
                                                           mode="drop")
        y = jax.lax.psum(y_partial, axes.model)   # combine expert columns
        aux = jax.lax.pmean(aux, axes.dp)
        return y, aux

    y, aux = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(axes.model, dp, None), P(axes.model, dp, None),
                  P(axes.model, None, dp), P(dp, None)),
        out_specs=(P(dp, None), P()),
        check_vma=False,
    )(p["router"], p["w_gate"], p["w_in"], p["w_out"], x2)
    return y, aux


def moe_apply(p: dict, cfg: ModelConfig, x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, d) → (y, aux_loss)."""
    from ..parallel.sharding import active_mesh
    B, S, d = x.shape
    x2 = x.reshape(B * S, d)
    mesh, axes = active_mesh()
    if (cfg.moe.impl == "shard_map" and mesh is not None):
        dsize = 1
        for a in axes.dp:
            dsize *= mesh.shape[a]
        # At decode-scale token counts the FSDP weight gather inside the
        # shard_map dominates (§Perf: llama4 decode regressed 3×); GSPMD
        # scatter moves tokens instead, which is right for tiny T.
        if (B * S) % dsize == 0 and (B * S) >= SHARD_MAP_MIN_TOKENS:
            y, aux = _moe_shard_map(p, cfg, x2, mesh, axes)
            return y.reshape(B, S, d), aux
    fn = _moe_scatter if cfg.moe.impl in ("scatter", "shard_map") \
        else _moe_einsum
    y, aux = fn(p, cfg, x2)
    return y.reshape(B, S, d), aux
