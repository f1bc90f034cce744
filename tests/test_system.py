"""End-to-end behaviour: the paper's scenario executed for real (placed CNN
inference over a simulated swarm) and placement↔sharding integration."""

import os
import pathlib
import subprocess
import sys

import jax
import numpy as np

from repro.core import (Problem, SnapshotView, evaluate, get_planner,
                        lenet_profile, solve_ould, to_stages, vgg16_profile)
from repro.core.mobility import RPGMobility, RPGParams
from repro.core.placement import balanced_stages, ould_pipeline_stages
from repro.core.profiles import lm_profile
from repro.core.radio import RadioParams, TpuLinkModel, rate_matrix
from repro.exec import compile_cache, compile_plan
from repro.launch import serve
from repro.models import cnn

MB = 1e6


def _swarm_problem(requests=6, mem_mb=128):
    mob = RPGMobility(RPGParams(n_uavs=8, area_m=120.0), seed=0)
    pos = mob.positions(1)[0]
    rng = np.random.default_rng(0)
    return Problem(lenet_profile(), np.full(8, mem_mb * MB),
                   np.full(8, 95e9), rate_matrix(pos, RadioParams()),
                   rng.integers(0, 2, requests).astype(np.int64),
                   compute_speed=np.full(8, 9.5e9))


def test_placed_inference_equals_local_inference():
    """Distributing layers across nodes must not change the prediction —
    the paper's central accuracy-preservation claim, checked end-to-end."""
    prob = _swarm_problem()
    sol = solve_ould(prob, solver="dp")
    params = cnn.lenet_init(jax.random.PRNGKey(0))
    fns = cnn.lenet_layers(params)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 326, 595, 3))
    local = cnn.apply_layers(fns, x)
    for r in range(prob.n_requests):
        if not sol.admitted[r]:
            continue
        stages = to_stages(sol.assign[r])
        y = x
        for st in stages:
            y = cnn.apply_layers(fns, y, st.layer_start, st.layer_end)
        np.testing.assert_allclose(np.asarray(y), np.asarray(local),
                                   rtol=1e-5, atol=1e-5)


def test_distribution_kicks_in_under_memory_pressure():
    prob = _swarm_problem(requests=8, mem_mb=128)
    sol = solve_ould(prob, solver="dp")
    ev = evaluate(prob, sol)
    assert ev.feasible
    assert ev.shared_bytes > 0  # hotspot sources must offload something


def test_ould_pipeline_stages_cover_model():
    prof = lm_profile("toy", n_layers=12, d_model=512, n_heads=8, n_kv=8,
                      d_ff=1024, vocab=32000, seq=256)
    stages = ould_pipeline_stages(prof, n_groups=8,
                                  hbm_bytes_per_group=prof.total_memory / 3,
                                  flops_cap_per_group=1e18)
    assert stages[0].layer_start == 0
    assert stages[-1].layer_end == prof.num_layers
    assert len(stages) >= 3  # memory cap forces a real pipeline


def test_tpu_link_model_prefers_neighbors():
    link = TpuLinkModel()
    coords = np.array([[0, 0], [1, 0], [8, 0]])
    pods = np.zeros(3, np.int64)
    r = link.rate_matrix(coords, pods)
    assert r[0, 1] > r[0, 2]             # 1 hop beats 8 hops
    r2 = link.rate_matrix(coords, np.array([0, 1, 0]))
    assert r2[0, 1] == link.dcn_bytes_per_s  # cross-pod rides DCN


def test_balanced_stages_flops_balance():
    prof = lm_profile("toy", n_layers=16, d_model=256, n_heads=4, n_kv=4,
                      d_ff=512, vocab=1000, seq=128)
    stages = balanced_stages(prof, 4)
    flops = prof.compute_vector()
    per_stage = [sum(flops[s.layer_start:s.layer_end]) for s in stages]
    assert len(stages) == 4
    assert max(per_stage) / max(min(per_stage), 1.0) < 3.0


ROOT = pathlib.Path(__file__).resolve().parents[1]


def test_chip_smoke_refuses_without_a_tpu():
    """No chip: a non-zero exit, a message, and no result line."""
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         cwd=ROOT, env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    assert '"ok"' not in out.stdout


def test_serve_main_executes_the_placed_cnn():
    """The CLI entry point, called with argv, serves the LM and runs the
    placed LeNet round whose outputs equal the one-node reference."""
    prev = compile_cache.cache_dir()
    try:
        run = serve.main(["--prompt-len", "8", "--steps", "3",
                          "--planner", "ould-dp-sparse", "--execute"])
    finally:                  # main turns the persistent cache on
        if prev is None:
            compile_cache.disable()
        else:
            compile_cache.enable(prev)
    assert run.generated.shape == (4, 3)
    admitted = np.flatnonzero(run.cnn_plan.admitted)
    assert admitted.size and run.graph.transfers
    ref = run.engine.sequential_reference(run.frames, list(admitted))
    for r in admitted:
        np.testing.assert_allclose(run.report.outputs[r], ref[r],
                                   rtol=1e-5, atol=1e-6)


def test_vgg16_pool_memory_forces_a_split():
    """At the serve pool's VGG16 node memory (the paper's 512 MB level) the
    ~1 GB model is admitted only split over nodes, with transfers."""
    n = 8
    link = TpuLinkModel()
    coords = np.stack([np.arange(n) % link.torus[0],
                       np.arange(n) // link.torus[0]], -1)
    rates = link.rate_matrix(coords, np.zeros(n, np.int64)) * 8.0
    prob = Problem(vgg16_profile(), np.full(n, serve.NODE_MEM_BYTES["vgg16"]),
                   np.full(n, 95e9), rates, np.array([0, 1, 0, 1]),
                   compute_speed=np.full(n, 9.5e9))
    plan = get_planner("ould-dp-sparse").plan(prob, SnapshotView(rates))
    assert plan.n_admitted >= 1
    for r in np.flatnonzero(plan.admitted):
        assert len(set(plan.assign[r].tolist())) >= 2
    assert compile_plan(plan).transfers
