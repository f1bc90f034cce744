"""Batched jitted min-plus DP kernel: one dispatch per epoch, not per request.

The sparse k-candidate DP (:mod:`repro.core.ould`) solves each request as an
``(M-1, k, k)`` min-plus sweep over pre-selected candidate nodes.  The sweep
is already array-shaped, but the sequential solver runs it request-at-a-time
in Python — at N = 1024 the per-request interpreter overhead (candidate
selection, the M-step Python loop over tiny k×k numpy ops) dominates the
epoch re-solve.  This module moves the sweep into a single jitted JAX kernel
that solves a whole *batch of rows* (one row per distinct request source) in
one dispatch:

* rows are stacked ``(S, M, k)`` candidate/validity arrays from
  :func:`~repro.core.ould._sparse_select`;
* the layer sweep runs the M-1 transitions as a statically unrolled loop of
  batched k×k min-plus blocks, with the transition tensor *gathered inside
  the kernel* (the ``spb`` matrix is pushed to the device once per topology
  and cached) and the infeasibility penalty / per-layer compute cost folded
  in exactly as the sequential kernel folds them;
* argmin backtracking recovers per-row placements (vectorized over rows on
  the host — it is O(S·M) index chasing, not worth a kernel).

Bit-identity contract
---------------------
The batched kernel must reproduce :func:`~repro.core.ould._sparse_run`
bit-for-bit — the admission decision of the greedy solve hangs on float
comparisons against the ``max_path_cost`` bar and the ``_BIG`` sentinel.
Three properties guarantee it:

1. all arithmetic runs in float64 (``jax.enable_x64(True)`` around
   trace and dispatch — the rest of the repo stays on default f32), with the
   same per-element operation order as the numpy reference (gather-multiply,
   then + penalty, then + compute, then + carried cost);
2. ``jnp.argmin`` and ``np.argmin`` both return the *first* minimum, so
   tie-breaking over the ascending-node-ordered candidate axis matches; the
   carried cost uses ``jnp.min``, whose value equals the element at the
   argmin (no NaNs can occur — costs are products and sums of non-negative
   finite rates plus {0, inf} penalties);
3. the element gathered for a transition is the identical ``spb`` float the
   numpy kernel reads.

Padding / bucketing contract
----------------------------
XLA compiles one executable per input shape.  The row count S varies every
epoch (it tracks the live request set), so rows are padded up to the next
power-of-two bucket (floor :data:`MIN_BUCKET`) before dispatch and sliced
back after: re-solving with a different S only recompiles when S crosses a
bucket boundary.  (M is pinned by the model profile and k by the ladder
level, so those axes are naturally stable.)  Padded rows carry benign zeros
and are never read back.  :func:`compile_count` counts the shapes the sweep
was traced for, so tests can pin the contract.
"""

from __future__ import annotations

import numpy as np

MIN_BUCKET = 8

_kernel = None      # lazily built jitted sweep (keeps jax off the cold path)
_n_traces = 0       # shapes the sweep was traced (and so compiled) for
_spb_cache: tuple | None = None   # (numpy spb, device spb) — `is`-keyed


def bucket_rows(n_rows: int) -> int:
    """Pad ``n_rows`` up to the next power-of-two bucket (≥ MIN_BUCKET)."""
    b = MIN_BUCKET
    while b < n_rows:
        b *= 2
    return b


def _build_kernel():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def sweep(spb, Kv, Ks, srcs, cand, pen, cc):
        """spb (N,N); srcs (S,); cand/pen (S,M,k); cc (M,N) or None
        → final (S,k) min-plus costs, backs (M-1,S,k) argmin back-pointers."""
        global _n_traces
        _n_traces += 1          # the body runs once per traced shape
        N = spb.shape[0]
        flat = spb.ravel()                         # flat take beats 2D gather
        c = Ks * jnp.take(flat, srcs[:, None] * N + cand[:, 0, :]) + pen[:, 0]
        if cc is not None:
            c = c + cc[0, cand[:, 0, :]]
        M = cand.shape[1]
        backs = []
        for j in range(1, M):                      # static unroll over layers
            tr = Kv[j - 1] * jnp.take(flat, cand[:, j - 1, :, None] * N
                                      + cand[:, j, None, :])
            tr = tr + pen[:, j, None, :]
            if cc is not None:
                tr = tr + cc[j, cand[:, j, :]][:, None, :]
            step = c[:, :, None] + tr              # (S, k_prev, k_cur)
            backs.append(jnp.argmin(step, axis=1))  # first min — numpy parity
            c = jnp.min(step, axis=1)
        if backs:
            return c, jnp.stack(backs)
        return c, jnp.zeros((0,) + c.shape, jnp.int64)

    return sweep


def _get_kernel():
    global _kernel
    if _kernel is None:
        _kernel = _build_kernel()
    return _kernel


def compile_count() -> int:
    """Number of distinct shapes the sweep kernel has compiled for (tests pin
    the padding contract: same bucket ⇒ no recompilation)."""
    return _n_traces


def _device_spb(spb: np.ndarray):
    """Push the seconds-per-bit matrix to the device once per topology.

    Keyed by object identity; holding the numpy reference keeps the id alive,
    so a stale hit is impossible.  One slot suffices — a solve works one
    topology at a time.
    """
    global _spb_cache
    import jax.numpy as jnp

    if _spb_cache is None or _spb_cache[0] is not spb:
        _spb_cache = (spb, jnp.asarray(spb))
    return _spb_cache[1]


def solve_batch(spb: np.ndarray, Ks: float, compute_cost: np.ndarray | None,
                srcs: np.ndarray, cand: np.ndarray, valid: np.ndarray,
                consts: tuple) -> tuple[list[np.ndarray | None], np.ndarray]:
    """Solve a batch of pruned DPs in one kernel dispatch.

    ``srcs`` (S,) request sources; ``cand``/``valid`` (S, M, k) stacked
    per-row candidate selections (:func:`~repro.core.ould._sparse_select`).
    Returns ``(paths, costs)`` — per row the argmin-backtracked placement
    (None when no finite path survives the feasibility penalty) and its
    cost, bit-identical to running :func:`~repro.core.ould._sparse_run` on
    each row sequentially.
    """
    import jax

    Kv = np.asarray(consts[0], np.float64)
    S, M, kk = cand.shape
    pen = np.where(valid, 0.0, np.inf)                        # (S, M, kk)
    Sp = bucket_rows(S)
    if Sp != S:
        srcs = np.concatenate([srcs, np.zeros(Sp - S, srcs.dtype)])
        cand = np.concatenate([cand, np.zeros((Sp - S, M, kk), cand.dtype)])
        pen = np.concatenate([pen, np.zeros((Sp - S, M, kk))])
    with jax.enable_x64(True):
        f, b = _get_kernel()(_device_spb(spb), Kv, np.float64(Ks),
                             srcs, cand, pen, compute_cost)
        final = np.asarray(f)[:S]
        backs = np.asarray(b)[:, :S]
    # Vectorized backtrack — mirrors _sparse_run's per-row argmin walk.
    rows = np.arange(S)
    ends = np.argmin(final, axis=1)
    finite = np.isfinite(final[rows, ends])
    nodes = np.empty((S, M), np.int64)
    idx = ends.copy()
    nodes[:, M - 1] = cand[rows, M - 1, idx]
    for j in range(M - 1, 0, -1):
        idx = backs[j - 1, rows, idx]
        nodes[:, j - 1] = cand[rows, j - 1, idx]
    paths: list[np.ndarray | None] = [
        nodes[q] if finite[q] else None for q in range(S)]
    costs = np.where(finite, final[rows, ends], np.inf)
    return paths, costs
