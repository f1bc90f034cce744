"""Byte-moving transport backends (repro.transport): protocol round trips,
engine equivalence across backends, persistent-compile-cache warm starts,
and the bandwidth-calibrated re-solve loop."""

import os
import pathlib

import jax
import numpy as np
import pytest

from repro.core import Problem, SnapshotView, Solution, get_planner, lenet_profile
from repro.core.mobility import RPGMobility, RPGParams
from repro.core.planner import Plan
from repro.core.radio import RadioParams, rate_matrix
from repro.exec import (ExecutionEngine, calibrated_problem, compile_cache,
                        compile_plan, layer_fns_for, link_payload_bytes,
                        measure_warm_start, stage_signature)
from repro.transport import (InProcTransport, LoopbackTransport,
                             MultiProcTransport, Transport, make_transport)

MB = 1e6
TOL = 1e-5
FRAME_HW = (326, 595, 3)      # lenet layer fns are input-shape-specific


def _uniform_problem(n_nodes=6, requests=2, seed=0, mem_mb=4096):
    mob = RPGMobility(RPGParams(n_uavs=n_nodes, area_m=120.0,
                                homogeneous=False), seed=seed)
    rates = rate_matrix(mob.positions(1, seed=seed)[0], RadioParams())
    sources = np.zeros(requests, np.int64)
    return Problem(lenet_profile(), np.full(n_nodes, mem_mb * MB),
                   np.full(n_nodes, 1e18), rates, sources,
                   compute_speed=np.full(n_nodes, 9.5e9))


def _manual_plan(prob, sizes_per_request):
    M = prob.n_layers
    R = len(sizes_per_request)
    assign = np.zeros((R, M), np.int64)
    for r, sizes in enumerate(sizes_per_request):
        assert sum(sizes) == M
        j = 0
        for node, size in enumerate(sizes):
            assign[r, j:j + size] = node
            j += size
    sol = Solution(assign, 0.0, "feasible", 0.0, np.ones(R, bool),
                   solver="manual")
    return Plan(sol, "manual", "snapshot", prob)


def _frames(rng, n):
    return rng.standard_normal((n, *FRAME_HW)).astype(np.float32)


# ---------------------------------------------------------------------------
# worker protocol and backend registry
# ---------------------------------------------------------------------------

def test_loopback_workers_are_real_processes():
    """Shipments echo exactly through >= 2 distinct worker OS processes."""
    rng = np.random.default_rng(0)
    with LoopbackTransport(n_workers=2) as tp:
        assert len(set(tp.worker_pids)) == 2
        assert os.getpid() not in tp.worker_pids
        for shape, dtype in (((7, 5), np.float32), ((64, 64, 3), np.float32),
                             ((11,), np.int64), ((3, 2), np.float64)):
            arr = (rng.standard_normal(shape) * 10).astype(dtype)
            res = tp.ship(0, 1, arr)
            assert res.moved
            assert res.nbytes == arr.nbytes
            got = np.asarray(res.array)
            assert got.dtype == arr.dtype and got.shape == arr.shape
            np.testing.assert_array_equal(got, arr)
        assert tp.moved_bytes > 0
        ls = tp.link_stats[(0, 1)]
        assert ls.n == 4 and ls.wall_s > 0 and ls.bytes_per_s > 0
    assert not tp.started         # context exit shut the workers down


def test_loopback_worker_ownership():
    tp = LoopbackTransport(n_workers=2, node_of={0: 0, 1: 0, 2: 1})
    assert tp.worker_of(0) == tp.worker_of(1) == 0
    assert tp.worker_of(2) == 1
    assert tp.worker_of(5) == 1   # unmapped nodes fall back to round-robin
    with pytest.raises(ValueError, match="at least one"):
        LoopbackTransport(n_workers=0)


def test_make_transport_registry():
    assert isinstance(make_transport("inproc"), InProcTransport)
    assert isinstance(make_transport("loopback"), LoopbackTransport)
    mp = make_transport("multiproc", group_of=np.array([0, 0, 1, 1]))
    assert isinstance(mp, MultiProcTransport)
    assert mp.n_workers == 2 and mp.worker_of(1) == 0 and mp.worker_of(3) == 1
    for name in ("inproc", "loopback", "multiproc"):
        assert isinstance(make_transport(name), Transport)
    with pytest.raises(ValueError, match="unknown transport"):
        make_transport("carrier-pigeon")


def test_multiproc_ships_through_jax_workers():
    """--jax workers land the buffer on their device before echoing."""
    rng = np.random.default_rng(1)
    with MultiProcTransport(group_of=np.array([0, 0, 1, 1])) as tp:
        tp.start()
        assert len(set(tp.worker_pids)) == 2
        assert all(b for b in tp.worker_backends)   # real JAX backends
        arr = rng.standard_normal((128, 64)).astype(np.float32)
        res = tp.ship(0, 3, arr)
        assert res.moved
        np.testing.assert_array_equal(np.asarray(res.array), arr)


# ---------------------------------------------------------------------------
# engine equivalence across backends
# ---------------------------------------------------------------------------

def test_inproc_is_bit_compatible_default():
    """The default backend reproduces the pre-transport path: the shipped
    array IS the consuming stage's input, nothing leaves the process."""
    engine = ExecutionEngine(layer_fns_for(lenet_profile()))
    assert isinstance(engine.transport, InProcTransport)
    tp = InProcTransport()
    arr = np.ones((4, 4), np.float32)
    res = tp.ship(0, 1, arr)
    assert res.array is arr and not res.moved
    assert tp.moved_bytes == 0
    assert res.wall_s >= 0 and tp.link_stats[(0, 1)].nbytes == arr.nbytes


def test_loopback_engine_outputs_bitwise_equal_to_inproc():
    """The tentpole's exactness gate: routing every transfer through worker
    OS processes changes only the timings, never a single output bit."""
    prob = _uniform_problem(requests=2)
    plan = _manual_plan(prob, [[3, 4], [1, 4, 2]])
    graph = compile_plan(plan)
    assert graph.transfers, "plan must have cut points to exercise shipping"
    fns = layer_fns_for(lenet_profile(), key=jax.random.PRNGKey(1))
    frames = _frames(np.random.default_rng(0), 2)

    ref = ExecutionEngine(fns).run(graph, frames)
    with LoopbackTransport(n_workers=2) as tp:
        report = ExecutionEngine(fns, transport=tp).run(graph, frames)
        assert len(set(tp.worker_pids)) == 2
        assert os.getpid() not in tp.worker_pids
        assert tp.moved_bytes > 0

    assert ref.transport == "inproc" and report.transport == "loopback"
    for r in graph.requests:
        assert np.array_equal(report.outputs[r], ref.outputs[r]), r
    # modeled comm decomposition is backend-independent ...
    np.testing.assert_allclose(report.comm_s, ref.comm_s, rtol=0, atol=0)
    # ... while the measured hop walls come from the actual byte movement
    assert all(tr.serialize_s > 0 for tr in report.transfers)
    assert len(report.transfers) == len(graph.transfers)


def test_transport_samples_cover_graph_links():
    """Every link the graph ships on shows up in the transport's realized
    bandwidth ledger — the coverage contract calibrate_rates relies on."""
    prob = _uniform_problem(requests=2)
    plan = _manual_plan(prob, [[3, 4], [2, 2, 1, 2]])
    graph = compile_plan(plan)
    payload = link_payload_bytes(graph)
    fns = layer_fns_for(lenet_profile(), key=jax.random.PRNGKey(2))
    with LoopbackTransport(n_workers=2) as tp:
        ExecutionEngine(fns, transport=tp).run(
            graph, _frames(np.random.default_rng(1), 2))
        assert set(tp.link_stats) == set(payload)
        for link, nbytes in payload.items():
            assert tp.link_stats[link].nbytes == pytest.approx(nbytes)
        spb = tp.measured_spb(prob.n_nodes)
        for s, d in payload:
            assert np.isfinite(spb[s, d]) and spb[s, d] > 0


# ---------------------------------------------------------------------------
# persistent compile cache
# ---------------------------------------------------------------------------

def test_warm_start_cache_hit_faster_than_cold(tmp_path):
    """Recompiling after a simulated process restart lands on the disk
    cache and is measurably faster than the cold compile."""
    fns = layer_fns_for(lenet_profile(), key=jax.random.PRNGKey(3))
    frame = _frames(np.random.default_rng(2), 1)[0]
    rep = measure_warm_start(fns, [(0, 3), (3, 7)], frame,
                             cache_dir=tmp_path / "cc")
    assert (tmp_path / "cc").is_dir()
    assert any((tmp_path / "cc").iterdir()), "nothing persisted to the cache"
    assert rep.warm_total_s < rep.cold_total_s
    assert rep.speedup > 1.2, rep.summary()
    assert len(rep.cold_s) == len(rep.warm_s) == 2


def test_warm_start_rejects_unchained_ranges(tmp_path):
    fns = layer_fns_for(lenet_profile())
    frame = np.zeros(FRAME_HW, np.float32)
    with pytest.raises(ValueError, match="chain from layer 0"):
        measure_warm_start(fns, [(2, 5)], frame, cache_dir=tmp_path)


def test_compile_cache_enable_restores(tmp_path):
    prev = compile_cache.cache_dir()
    try:
        d = compile_cache.enable(tmp_path / "cc2")
        assert compile_cache.is_enabled() and compile_cache.cache_dir() == d
    finally:
        if prev is None:
            compile_cache.disable()
        else:
            compile_cache.enable(prev)
    assert compile_cache.cache_dir() == prev


@pytest.mark.parametrize("env_set", [True, False])
def test_compile_cache_dir_env_over_argument(tmp_path, monkeypatch, env_set):
    """JAX_COMPILATION_CACHE_DIR is the cache whenever it is set; without
    it an argument wins, and with neither the fixed in-checkout path."""
    prev = compile_cache.cache_dir()
    env = tmp_path / "from_env"
    if env_set:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(env))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = compile_cache.enable(tmp_path / "from_arg")
        assert got == (env if env_set else tmp_path / "from_arg")
        default = compile_cache.enable()
        assert default == (env if env_set else compile_cache.DEFAULT_DIR)
        assert compile_cache.cache_dir() == default
    finally:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        if prev is None:
            compile_cache.disable()
        else:
            compile_cache.enable(prev)
    root = pathlib.Path(__file__).resolve().parents[1]
    assert compile_cache.DEFAULT_DIR == root / ".jax_cache"


def test_multiproc_refuses_a_tpu_parent(monkeypatch):
    """A chip serves one process: under a TPU parent the JAX workers could
    never get it, so start() refuses before spawning anything."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    tp = MultiProcTransport(n_workers=2)
    with pytest.raises(RuntimeError, match="holds the TPU"):
        tp.start()
    assert not tp._procs and not tp.started


def test_engine_warm_start_compiles_signature():
    """warm_start pre-compiles a stage signature (the churn-rejoin path)."""
    prob = _uniform_problem(requests=1)
    graph = compile_plan(_manual_plan(prob, [[1, 4, 2]]))
    sig = stage_signature(graph)
    engine = ExecutionEngine(layer_fns_for(lenet_profile(),
                                           key=jax.random.PRNGKey(4)))
    wall = engine.warm_start(sig, np.zeros(FRAME_HW, np.float32))
    assert wall > 0
    for s, e in sig:
        assert (s, e) in engine._closures


# ---------------------------------------------------------------------------
# bandwidth-calibrated re-solves
# ---------------------------------------------------------------------------

def test_comm_calibration_closes_the_loop():
    """Realized per-link bandwidth from a loopback run replaces the analytic
    rates, the provenance rides into the re-solved Plan.problem, and the
    modeled-vs-realized comm gap collapses on the re-run."""
    mob = RPGMobility(RPGParams(n_uavs=8, area_m=150.0, homogeneous=False),
                      seed=0)
    rates = rate_matrix(mob.positions(1)[0], RadioParams())
    rng = np.random.default_rng(0)
    sources = rng.integers(0, 3, 4).astype(np.int64)
    prob = Problem(lenet_profile(), np.full(8, 128 * MB), np.full(8, 95e9),
                   rates, sources, compute_speed=np.full(8, 9.5e9))
    assert prob.comm_source == "analytic"
    fns = layer_fns_for(lenet_profile(), key=jax.random.PRNGKey(0))
    frames = _frames(rng, 4)
    planner = get_planner("ould-dp")

    with LoopbackTransport(n_workers=2) as tp:
        engine = ExecutionEngine(fns, transport=tp)
        plan = planner.plan(prob, SnapshotView(rates))
        graph = compile_plan(plan)
        assert graph.transfers, "scenario must ship bytes to calibrate comm"
        report = engine.run(
            graph, frames, predicted_s=np.asarray(plan.evaluate().per_request_s))

        cal_prob, recon = calibrated_problem(prob, report, transport=tp)
        assert recon.transport == "loopback"
        assert recon.link_measured_spb and recon.comm_mae_s > 0
        assert "comm[loopback]" in recon.summary()
        assert cal_prob.comm_source == "measured:loopback"
        # sampled links carry realized rates, unsampled keep analytic ones
        for (s, d), spb in recon.link_measured_spb.items():
            assert cal_prob.transfer_cost()[s, d] == pytest.approx(spb)
        untouched = [(s, d) for s in range(8) for d in range(8) if s != d
                     and (s, d) not in recon.link_measured_spb]
        sd = untouched[0]
        assert cal_prob.rates[sd] == pytest.approx(rates[sd])

        replan = planner.plan(cal_prob, SnapshotView(cal_prob.rates))
        assert replan.problem.comm_source == "measured:loopback"
        rereport = engine.run(
            regraph := compile_plan(replan), frames,
            predicted_s=np.asarray(replan.evaluate().per_request_s))
        _, recon2 = calibrated_problem(cal_prob, rereport, transport=tp)
        assert regraph.requests
        # analytic radio delays are orders of magnitude off localhost
        # sockets; after substitution the modeled delays track realized
        assert recon2.comm_mae_s < recon.comm_mae_s


def test_calibrate_rates_ignores_bogus_samples():
    from repro.exec import calibrate_rates
    prob = _uniform_problem(requests=1)
    cal = calibrate_rates(prob, {(0, 0): 1e-9, (0, 1): np.nan,
                                 (1, 2): -1.0, (99, 0): 1e-9},
                          source="measured:test")
    np.testing.assert_array_equal(cal.rates, prob.rates)
    assert cal.comm_source == "measured:test"
    assert prob.comm_source == "analytic"      # never mutated in place
