"""Swarm topology and frame generators, copied from the program so that a
later change to it cannot move the yardstick.

* :func:`rpg_positions` — the Reference-Point-Group trajectory of
  ``repro.core.mobility.RPGMobility.positions`` (paper §III-C);
* :func:`rate_matrix` — the air-to-air rates of
  ``repro.core.radio.rate_matrix`` (paper Eq. 1);
* :func:`hotspot_sources` — the hotspot source draw of
  ``benchmarks/common.py::snapshot_problem``;
* :func:`frames` — standard-normal RGB frames, as ``launch/serve.py`` makes
  them.
"""

from __future__ import annotations

import numpy as np

# Paper §IV radio: 20 MHz, 0.1 W, LoS air-to-air path loss, 300 m range.
RADIO = dict(bandwidth_hz=20e6, tx_power_w=0.1, noise_w=1e-13,
             path_loss_exp=2.7, ref_gain=1e-4, max_range_m=300.0,
             interference_frac=0.1)
# RPG group motion (paper §III-C).
RPG = dict(altitude_m=50.0, leader_speed_mps=5.0, member_radius_m=25.0,
           member_jitter_mps=1.0, step_s=1.0)


def rpg_positions(n_uavs: int, area_m: float, num_steps: int, seed: int, *,
                  homogeneous: bool) -> np.ndarray:
    """(T, N, 3) planned positions for t = 0..T-1 of one RPG group whose
    leader sweeps the area corner to corner and back.  ``homogeneous``
    freezes the members' offsets (paper Fig. 2a); otherwise each step adds
    a jitter inside the liberty radius (Fig. 2b)."""
    p = RPG
    rng = np.random.default_rng(seed)
    r = rng.uniform(0, p["member_radius_m"], n_uavs)
    theta = rng.uniform(0, 2 * np.pi, n_uavs)
    offsets = np.stack([r * np.cos(theta), r * np.sin(theta)], -1)
    start = np.array([p["member_radius_m"]] * 2)
    end = np.array([area_m - p["member_radius_m"]] * 2)
    span = np.linalg.norm(end - start)
    period = 2.0 * span / p["leader_speed_mps"]
    # The program's positions(T, seed=seed) draws the jitter from a second
    # generator seeded alike.
    rng = np.random.default_rng(seed)
    out = np.zeros((num_steps, n_uavs, 3))
    for t in range(num_steps):
        frac = ((t * p["step_s"]) % period) / period * 2.0
        if frac > 1.0:
            frac = 2.0 - frac
        leader = start + frac * (end - start)
        if not homogeneous:
            drift = rng.normal(0.0, p["member_jitter_mps"] * p["step_s"],
                               offsets.shape)
            offsets = offsets + drift
            norm = np.linalg.norm(offsets, axis=-1, keepdims=True)
            offsets = offsets * np.minimum(
                1.0, p["member_radius_m"] / np.maximum(norm, 1e-9))
        out[t, :, :2] = leader + offsets
        out[t, :, 2] = p["altitude_m"]
    return out


def rate_matrix(positions: np.ndarray) -> np.ndarray:
    """ρ_{i,k} = B·log2(1 + Γ_{i,k}) in bits/s; ∞ on the diagonal, 0 past
    the radio range."""
    p = RADIO
    n = positions.shape[0]
    dist = np.linalg.norm(positions[:, None, :] - positions[None, :, :],
                          axis=-1)
    prx = p["tx_power_w"] * p["ref_gain"] * np.maximum(dist, 1.0) ** (
        -p["path_loss_exp"])
    np.fill_diagonal(prx, 0.0)
    total_at_k = prx.sum(axis=0)
    sinr = np.zeros((n, n))
    for i in range(n):
        interference = (total_at_k - prx[i]) * p["interference_frac"]
        sinr[i] = prx[i] / (p["noise_w"] + interference)
    np.fill_diagonal(sinr, 0.0)
    sinr[dist > p["max_range_m"]] = 0.0
    rho = p["bandwidth_hz"] * np.log2(1.0 + sinr)
    np.fill_diagonal(rho, np.inf)
    return rho


def hotspot_sources(n_requests: int, hotspots: int, n_uavs: int,
                    seed: int) -> np.ndarray:
    """Requests originate uniformly at the first ``hotspots`` UAVs."""
    rng = np.random.default_rng(seed)
    return rng.integers(0, min(hotspots, n_uavs), n_requests).astype(np.int64)


def frames(n: int, shape, seed: int) -> np.ndarray:
    """(n, H, W, C) float32 standard-normal frames."""
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n, *shape)).astype(np.float32)

