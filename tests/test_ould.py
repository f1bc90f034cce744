"""OULD / OULD-MP optimization: optimality, constraints, admission."""

import itertools
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core import (Problem, RPGMobility, RPGParams, SnapshotView,
                        evaluate, rate_matrix, solve_heuristic, solve_ould)
from repro.core.profiles import LayerProfile, ModelProfile


def toy_profile(m=4, mem=10.0, comp=5.0):
    outs = [8.0, 4.0, 2.0, 1.0, 1.0, 1.0][:m]
    layers = tuple(LayerProfile(f"l{j}", mem, comp, outs[j]) for j in range(m))
    return ModelProfile("toy", layers, input_bytes=16.0)


def toy_problem(n=3, r=2, mem_cap=30.0, seed=0, m=4):
    rng = np.random.default_rng(seed)
    pos = rng.uniform(0, 80, (n, 3))
    pos[:, 2] = 50.0
    return Problem(toy_profile(m), np.full(n, mem_cap), np.full(n, 1e9),
                   rate_matrix(pos), np.arange(r) % n)


def brute_force(prob):
    spb = prob.transfer_cost()
    K = prob.profile.output_vector()
    mem = prob.profile.memory_vector()
    N, M, R = prob.n_nodes, prob.n_layers, prob.n_requests
    best = np.inf
    for a in itertools.product(range(N), repeat=R * M):
        a = np.array(a).reshape(R, M)
        load = np.zeros(N)
        for r in range(R):
            for j in range(M):
                load[a[r, j]] += mem[j]
        if (load > prob.mem_cap + 1e-9).any():
            continue
        cost = 0.0
        for r in range(R):
            src = int(prob.sources[r])
            if a[r, 0] != src:
                cost += prob.profile.input_bytes * spb[src, a[r, 0]]
            for j in range(M - 1):
                if a[r, j + 1] != a[r, j]:
                    cost += K[j] * spb[a[r, j], a[r, j + 1]]
        best = min(best, cost)
    return best


def test_ilp_matches_bruteforce():
    prob = toy_problem()
    sol = solve_ould(prob)
    assert sol.status == "optimal"
    assert sol.objective == pytest.approx(brute_force(prob), rel=1e-6)


def test_gamma_relaxation_exact():
    """γ continuous in [0,1] must not change the optimum (big-M argument)."""
    for seed in range(3):
        prob = toy_problem(seed=seed)
        a = solve_ould(prob, gamma_relaxed=True).objective
        b = solve_ould(prob, gamma_relaxed=False).objective
        assert a == pytest.approx(b, rel=1e-9)


def test_tight_constraints_equivalent():
    prob = toy_problem(seed=1)
    a = solve_ould(prob, tight=True).objective
    b = solve_ould(prob, tight=False).objective
    assert a == pytest.approx(b, rel=1e-9)


def test_capacity_constraints_respected():
    prob = toy_problem(n=4, r=3, mem_cap=25.0)
    sol = solve_ould(prob)
    ev = evaluate(prob, sol)
    assert ev.feasible


def test_admission_sheds_when_over_capacity():
    # 2 requests × 4 layers × 10B > 3 nodes × 20B ⇒ at most 1 admitted
    prob = toy_problem(n=3, r=2, mem_cap=20.0)
    sol = solve_ould(prob)
    assert sol.status.startswith("rejected")
    assert sol.n_admitted == 1
    assert evaluate(prob, sol).feasible


def test_dp_optimal_when_capacity_slack():
    prob = toy_problem(n=3, r=1, mem_cap=1e9)
    ilp = solve_ould(prob)
    dp = solve_ould(prob, solver="dp")
    assert dp.objective == pytest.approx(ilp.objective, rel=1e-6)


def test_heuristics_feasible_and_dominated():
    prob = toy_problem(n=4, r=3, mem_cap=25.0, seed=2)
    opt = solve_ould(prob)
    for kind in ("nearest", "hrm", "nearest_hrm"):
        sol = solve_heuristic(prob, kind)
        ev = evaluate(prob, sol)
        assert ev.feasible
        if sol.n_admitted == opt.n_admitted == prob.n_requests:
            assert evaluate(prob, opt).comm_latency_s <= ev.comm_latency_s + 1e-9


def test_exactly_one_constraint():
    prob = toy_problem()
    sol = solve_ould(prob)
    # every admitted request has every layer on exactly one node (assign is
    # a function) and the path starts from a real node id
    assert sol.assign.shape == (prob.n_requests, prob.n_layers)
    assert (sol.assign >= 0).all() and (sol.assign < prob.n_nodes).all()


def test_ould_mp_avoids_predicted_disconnection():
    """A pair that disconnects mid-horizon must not carry any transfer."""
    prof = toy_profile(m=2, mem=10.0)
    # node 2 drifts out of range at t=1; OULD-MP must not route via node 2
    rates = np.full((2, 3, 3), 1e8)
    for t in range(2):
        np.fill_diagonal(rates[t], np.inf)
    rates[1, 0, 2] = rates[1, 2, 0] = 0.0
    rates[1, 1, 2] = rates[1, 2, 1] = 0.0
    prob = Problem(prof, np.full(3, 10.0), np.full(3, 1e9), rates,
                   np.zeros(1, np.int64))
    sol = solve_ould(prob)
    assert 2 not in sol.assign[0]


def test_mobility_rates_deterministic():
    mob = RPGMobility(RPGParams(n_uavs=5), seed=42)
    a = mob.predicted_rates(3, seed=7)
    b = RPGMobility(RPGParams(n_uavs=5), seed=42).predicted_rates(3, seed=7)
    np.testing.assert_allclose(a, b)


# ---------------------------------------------------------------------------
# capacity repair rules
# ---------------------------------------------------------------------------

def _contended_problem():
    """Three ~equal requests over caps where the halving repair's geometric
    overshoot excludes placements the gentle rule keeps reachable."""
    layers = tuple(LayerProfile(f"l{j}", m, 5.0, o) for j, (m, o) in
                   enumerate([(14.0, 6.0), (15.0, 2.0), (14.0, 2.0)]))
    prof = ModelProfile("toy", layers, input_bytes=16.0)
    rng = np.random.default_rng(0)
    pos = rng.uniform(0, 80, (3, 3))
    pos[:, 2] = 50.0
    return Problem(prof, np.array([50.0, 31.0, 39.0]), np.full(3, 1e9),
                   rate_matrix(pos), np.array([1, 2, 0], np.int64))


def _repair_stage_admitted(prob, rule):
    """Requests the lattice DP and its repair loop admit under ``rule``,
    placed in order against residual capacity: the ladder without its
    run-aware last step."""
    from repro.core.ould import _place_request
    mem = prob.profile.memory_vector()
    comp = prob.profile.compute_vector()
    mem_left = prob.mem_cap.astype(float).copy()
    comp_left = prob.comp_cap.astype(float).copy()
    n = 0
    for src in prob.sources:
        path, _ = _place_request(prob.transfer_cost(),
                                 prob.profile.output_vector(),
                                 prob.profile.input_bytes, int(src), mem,
                                 comp, mem_left, comp_left, None,
                                 capacity_repair=rule)
        if path is not None:
            n += 1
            for j, i in enumerate(path):
                mem_left[i] -= mem[j]
                comp_left[i] -= comp[j]
    return n


def test_gentle_repair_admits_strictly_more_under_contention():
    """`capacity_repair="gentle"` sheds `load − min hosted layer` (with a
    largest-layer peel when that cannot strictly shrink) instead of halving
    — on this crafted contention scenario its repair stage admits strictly
    more requests, while the default stays the pinned halving rule.  The
    run-aware last step of the ladder then admits under either rule at
    least what the gentle repair does."""
    prob = _contended_problem()
    assert (_repair_stage_admitted(prob, "gentle")
            > _repair_stage_admitted(prob, "halve"))
    halve = solve_ould(prob, solver="dp")
    default = solve_ould(prob, solver="dp", capacity_repair="halve")
    gentle = solve_ould(prob, solver="dp", capacity_repair="gentle")
    np.testing.assert_array_equal(halve.assign, default.assign)
    assert int(halve.admitted.sum()) >= _repair_stage_admitted(prob, "gentle")
    assert int(gentle.admitted.sum()) >= _repair_stage_admitted(prob,
                                                                "gentle")
    # every admission still respects the joint per-node load
    mem = np.asarray(prob.profile.memory_vector())
    for sol in (halve, gentle):
        load = np.zeros(prob.n_nodes)
        for r in range(prob.n_requests):
            if sol.admitted[r]:
                for j, i in enumerate(sol.assign[r]):
                    load[i] += mem[j]
        assert (load <= prob.mem_cap + 1e-9).all()


def test_capacity_repair_validated_and_threads_through_solvers():
    prob = _contended_problem()
    with pytest.raises(ValueError, match="capacity_repair"):
        solve_ould(prob, solver="dp", capacity_repair="nope")
    from repro.core import IncrementalSolver
    inc = IncrementalSolver(prob.profile, prob.mem_cap, prob.comp_cap,
                            solver="dp", capacity_repair="gentle")
    sol, _ = inc.solve(prob.rates, prob.sources)
    gentle = solve_ould(prob, solver="dp", capacity_repair="gentle")
    assert int(sol.admitted.sum()) == int(gentle.admitted.sum())


# ---------------------------------------------------------------------------
# the run-aware last step of the placement ladder
# ---------------------------------------------------------------------------

CHIP = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"


def _split_cams4(profile, sources=(0, 1, 1, 0)):
    """The paper's deployment at its high memory level: 16 UAVs of 512 MB
    and 95 GFLOP a decision period in a 100 m area, camera streams sourced
    at two hotspot UAVs."""
    n = 16
    pos = RPGMobility(RPGParams(n_uavs=n, area_m=100.0, homogeneous=True),
                      seed=0).positions(1, seed=0)[0]
    return Problem(profile, np.full(n, 512e6), np.full(n, 95e9),
                   rate_matrix(pos), np.asarray(sources, np.int64),
                   compute_speed=np.full(n, 9.5e9))


def _within_capacity(prob, sol):
    mem = np.asarray(prob.profile.memory_vector())
    comp = np.asarray(prob.profile.compute_vector())
    m, c = np.zeros(prob.n_nodes), np.zeros(prob.n_nodes)
    for r in np.flatnonzero(sol.admitted):
        np.add.at(m, sol.assign[r], mem)
        np.add.at(c, sol.assign[r], comp)
    return bool((m <= prob.mem_cap + 1e-9).all()
                and (c <= prob.comp_cap + 1e-9).all())


@pytest.mark.parametrize("planner", ["ould-dp", "ould-dp-sparse",
                                     "incremental", "incremental-sparse"])
def test_uniform_blocks_are_placed_in_runs(planner):
    """12 equal blocks of 12.2 GFLOP (ViT-B/16 at 741 tokens) sum past a
    node's 95 GFLOP although each fits: the lattice DP stacks them on one
    node and its repair, under either rule, shrinks every node below one
    block.  The run-aware step places every stream in runs that fit."""
    from repro.core import get_planner, vitb16_profile
    prob = _split_cams4(vitb16_profile())
    for rule in ("halve", "gentle"):
        assert _repair_stage_admitted(prob, rule) == 0
    plan = get_planner(planner).plan(prob, SnapshotView(prob.rates),
                                     request_ids=list(range(4)))
    assert plan.n_admitted == 4
    assert _within_capacity(prob, plan.solution)
    for r in range(4):
        path = plan.solution.assign[r]
        assert len(set(path.tolist())) >= 2
        # runs: a node hosts one contiguous range of a frame's units
        starts = np.flatnonzero(np.diff(path)) + 1
        assert len(set(path.tolist())) == len(starts) + 1
    assert plan.solution.n_run_placed == 4
    if planner != "ould-dp":                # the dense cold solve has no stats
        assert plan.solve_stats.n_run_placed == 4


def test_run_placed_count_rides_the_solve_span():
    from repro.core import vitb16_profile
    from repro.obs import Tracer
    from repro.runtime.serve import AdmissionController
    prob = _split_cams4(vitb16_profile())
    tr = Tracer(1 << 12)
    ctrl = AdmissionController("incremental-sparse", tracer=tr)
    ids = list(range(4))
    first = ctrl.admit(prob, prob.rates, request_ids=ids)
    again = ctrl.admit(prob, prob.rates, request_ids=ids)
    assert first.solve_stats.n_run_placed == 4
    assert again.solve_stats.n_run_placed == 0      # kept, not re-placed
    rich = [tr._rich[k] for k in sorted(tr._rich)
            if "n_run_placed" in tr._rich[k]]
    assert [r["n_run_placed"] for r in rich] == [4, 0]


def test_vgg16_split_cams4_plan_is_pinned():
    """The VGG16 cell's plan as it stood before the run-aware step: the
    ladder admits all four streams without it, so it never runs."""
    sys.path.insert(0, str(CHIP))
    from harness import runtime
    from repro.exec import compile_plan
    from repro.runtime.serve import AdmissionController
    driver = runtime.load_module(CHIP / "drivers" / "camera_rounds.py")
    cfg = runtime.load_json(CHIP / "configs" / "vgg16.json")
    tr = runtime.load_json(CHIP / "traffic" / "split_cams4.json")
    prob = driver.deployment(cfg, tr)
    plan = AdmissionController(tr["planner"]).admit(
        prob, SnapshotView(prob.rates), request_ids=list(range(4)))
    graph = compile_plan(plan)
    assert (len(graph.tasks), len(graph.transfers)) == (34, 36)
    assert plan.solve_stats.n_run_placed == 0
    assert plan.solution.assign.tolist() == [
        [8, 6, 8, 8, 6, 8, 8, 6, 6, 8, 8, 6, 6, 8, 8, 8, 8, 7],
        [0, 10, 0, 0, 10, 0, 0, 10, 10, 0, 0, 10, 10, 0, 0, 0, 0, 5],
        [1, 0, 0, 8, 14, 8, 8, 14, 14, 8, 8, 14, 14, 8, 8, 8, 8, 12],
        [1, 1, 1, 1, 1, 9, 9, 1, 1, 9, 9, 1, 1, 9, 9, 9, 9, 15]]
