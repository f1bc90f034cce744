"""Plan-faithful execution engine (repro.exec): numeric equivalence to the
sequential reference across uniform and non-uniform cuts, stage dedup,
transfer pricing consistency, and measured-latency calibration."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (Problem, SnapshotView, Solution, get_planner,
                        lenet_profile, vgg16_profile)
from repro.core.mobility import RPGMobility, RPGParams
from repro.core.planner import Plan
from repro.core.radio import RadioParams, rate_matrix
from repro.exec import (ExecutionEngine, calibrated_problem, coalesce_graphs,
                        compile_plan, layer_fns_for)
from repro.exec.engine import StageTiming, TransferRecord
from repro.exec.stage_graph import stage_signature
from repro.models import cnn
from repro.transport import make_transport

MB = 1e6
TOL = 1e-5

# Non-uniform 2/3/4-stage cuts per model (stage layer counts, each sums to M).
CUTS = {
    "lenet": ([3, 4], [1, 4, 2], [2, 2, 1, 2]),        # M = 7 units
    "vgg16": ([5, 13], [2, 9, 7], [1, 6, 4, 7]),       # M = 18 units
}


def _uniform_problem(profile, n_nodes=6, requests=2, seed=0):
    mob = RPGMobility(RPGParams(n_uavs=n_nodes, area_m=120.0,
                                homogeneous=False), seed=seed)
    rates = rate_matrix(mob.positions(1, seed=seed)[0], RadioParams())
    sources = np.zeros(requests, np.int64)
    return Problem(profile, np.full(n_nodes, 4096 * MB),
                   np.full(n_nodes, 1e18), rates, sources,
                   compute_speed=np.full(n_nodes, 9.5e9))


def _manual_plan(prob, sizes_per_request):
    """A hand-built plan: request r runs stage s's layers on node s (so every
    cut point crosses a link)."""
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


def _frames(rng, n, hw):
    return rng.standard_normal((n, *hw)).astype(np.float32)


@pytest.mark.parametrize("model,hw", [("lenet", (326, 595, 3)),
                                      ("vgg16", (48, 64, 3))])
def test_engine_matches_sequential_across_cuts(model, hw):
    """Executed output == sequential apply_layers for 2/3/4-stage
    non-uniform cuts (the satellite acceptance matrix)."""
    profile = (lenet_profile() if model == "lenet" else vgg16_profile())
    prob = _uniform_problem(profile)
    fns = layer_fns_for(profile, key=jax.random.PRNGKey(1))
    engine = ExecutionEngine(fns)
    rng = np.random.default_rng(0)
    for sizes in CUTS[model]:
        plan = _manual_plan(prob, [sizes, sizes])
        graph = compile_plan(plan)
        assert len(graph.tasks) == len(sizes)          # both requests batch
        assert graph.n_shared == len(sizes)            # dedup across requests
        frames = _frames(rng, 2, hw)
        report = engine.run(graph, frames)
        ref = engine.sequential_reference(frames, graph.requests)
        for r in graph.requests:
            err = np.abs(report.outputs[r] - ref[r]).max()
            assert err < TOL, (model, sizes, r, err)
        # every cut point shipped one boundary activation per request
        assert len(graph.transfers) == 2 * (len(sizes) - 1)


def test_engine_mixed_cuts_one_graph():
    """Requests with DIFFERENT cuts in one graph stay independent and
    correct (no cross-request batching of unequal stages)."""
    profile = lenet_profile()
    prob = _uniform_problem(profile, requests=3)
    fns = layer_fns_for(profile, key=jax.random.PRNGKey(2))
    engine = ExecutionEngine(fns)
    rng = np.random.default_rng(1)
    plan = _manual_plan(prob, [[3, 4], [1, 4, 2], [7]])
    graph = compile_plan(plan)
    frames = _frames(rng, 3, (326, 595, 3))
    report = engine.run(graph, frames)
    ref = engine.sequential_reference(frames, graph.requests)
    for r in graph.requests:
        assert np.abs(report.outputs[r] - ref[r]).max() < TOL
    sig = stage_signature(graph)
    assert (0, 7) in sig and (0, 3) in sig and (0, 1) in sig


def test_planner_plans_execute_equivalently():
    """The acceptance matrix: every plan a registered planner emits on a
    fixed-seed scenario executes numerically equivalent to sequential."""
    profile = lenet_profile()
    mob = RPGMobility(RPGParams(n_uavs=8, area_m=150.0, homogeneous=False),
                      seed=0)
    rates = rate_matrix(mob.positions(1)[0], RadioParams())
    rng = np.random.default_rng(0)
    sources = rng.integers(0, 3, 5).astype(np.int64)
    prob = Problem(profile, np.full(8, 128 * MB), np.full(8, 95e9), rates,
                   sources, compute_speed=np.full(8, 9.5e9))
    fns = layer_fns_for(profile, key=jax.random.PRNGKey(0))
    engine = ExecutionEngine(fns)
    frames = _frames(rng, 5, (326, 595, 3))
    for name in ("ould-dp", "ould-dp-sparse", "nearest", "hrm"):
        plan = get_planner(name).plan(prob, SnapshotView(rates))
        assert plan.n_admitted > 0, name
        graph = compile_plan(plan)
        report = engine.run(graph, frames)
        ref = engine.sequential_reference(frames, graph.requests)
        for r in graph.requests:
            err = np.abs(report.outputs[r] - ref[r]).max()
            assert err < TOL, (name, r, err)


def test_transfer_delays_match_paper_objective():
    """Graph transfer pricing sums to the evaluation's comm latency — the
    executed decomposition uses the exact coefficients OULD minimized."""
    profile = lenet_profile()
    prob = _uniform_problem(profile, requests=2)
    plan = _manual_plan(prob, [[3, 4], [2, 2, 1, 2]])
    graph = compile_plan(plan)
    ev = plan.evaluate()
    total = sum(tr.delay_s for tr in graph.transfers)
    assert total == pytest.approx(ev.comm_latency_s, rel=1e-9)
    for r in graph.requests:
        assert graph.transfer_delay_s(r) >= 0.0


def test_topological_task_order():
    """Every transfer's producer stage precedes its consumer stage."""
    profile = lenet_profile()
    prob = _uniform_problem(profile, requests=2)
    plan = _manual_plan(prob, [[1, 4, 2], [3, 4]])
    graph = compile_plan(plan)
    pos = {t.key: i for i, t in enumerate(graph.tasks)}
    for tr in graph.transfers:
        producer = max(i for k, i in pos.items()
                       if k[0] == tr.src_node and k[2] == tr.layer)
        consumer = min(i for k, i in pos.items()
                       if k[0] == tr.dst_node and k[1] == tr.layer)
        assert producer < consumer


def test_coalesce_graphs_batches_across_arrival_rounds():
    """Three admission rounds of the same hotspot cut collapse to one
    launch per stage; request ids shift by the round offsets."""
    profile = lenet_profile()
    prob = _uniform_problem(profile, requests=2)
    graphs = [compile_plan(_manual_plan(prob, [[3, 4], [3, 4]]))
              for _ in range(3)]
    merged = coalesce_graphs(graphs)
    assert merged.n_requests == 6
    assert merged.requests == (0, 1, 2, 3, 4, 5)
    # same stages as one round — six requests ride two launches
    assert len(merged.tasks) == 2
    assert all(t.requests == (0, 1, 2, 3, 4, 5) for t in merged.tasks)
    assert sum(len(g.tasks) for g in graphs) == 6      # 3× launch reduction
    # transfers carried over verbatim, re-identified
    assert len(merged.transfers) == 3 * len(graphs[0].transfers)
    base = {(tr.src_node, tr.dst_node, tr.layer, tr.nbytes, tr.delay_s)
            for tr in graphs[0].transfers}
    for tr in merged.transfers:
        assert (tr.src_node, tr.dst_node, tr.layer, tr.nbytes,
                tr.delay_s) in base


def test_coalesce_graphs_execution_equivalent():
    """Batched-across-arrival execution matches per-round execution on the
    same frames (the tentpole's exactness criterion)."""
    profile = lenet_profile()
    prob = _uniform_problem(profile, requests=2)
    fns = layer_fns_for(profile, key=jax.random.PRNGKey(3))
    engine = ExecutionEngine(fns)
    rng = np.random.default_rng(7)
    rounds = [compile_plan(_manual_plan(prob, [[3, 4], [1, 4, 2]]))
              for _ in range(2)]
    frames = _frames(rng, 4, (326, 595, 3))
    merged = coalesce_graphs(rounds)
    got = engine.run(merged, frames)
    for i, g in enumerate(rounds):
        solo = engine.run(g, frames[2 * i: 2 * i + 2])
        for r in g.requests:
            err = np.abs(got.outputs[r + 2 * i] - solo.outputs[r]).max()
            assert err < TOL, (i, r, err)
        # link pricing identical: coalescing never reroutes a transfer
        for r in g.requests:
            assert got.comm_s[r + 2 * i] == pytest.approx(solo.comm_s[r])
    # fewer launches than the per-round executions combined
    assert len(merged.tasks) < sum(len(g.tasks) for g in rounds)


def _row_split_run(engine, graph, frames):
    """The engine's former hand-off, kept as a reference: every launch
    output split into rows by eager indexing (``y[b][None]``), every answer
    fetched by it.  Walls are left at 0."""
    acts = {r: jnp.asarray(frames[r][None]) for r in graph.requests}
    links = {(link.request, link.layer): link for link in graph.transfers}
    timings, records = [], []
    for task in graph.tasks:
        for r in task.requests:
            link = links.get((r, task.layer_start))
            if link is not None:
                acts[r] = engine.transport.ship(link.src_node, link.dst_node,
                                                acts[r]).array
                records.append(TransferRecord(
                    link.request, link.src_node, link.dst_node, link.layer,
                    link.nbytes, link.delay_s, 0.0))
        rows = [acts[r] for r in task.requests]
        x = rows[0] if len(rows) == 1 else jnp.concatenate(rows)
        y = engine.closure(task.layer_start, task.layer_end)(x)
        timings.append(StageTiming(task.node, task.layer_start,
                                   task.layer_end, len(rows), 0.0))
        for b, r in enumerate(task.requests):
            acts[r] = y[b][None]
    outputs = {r: np.asarray(acts[r][0]) for r in graph.requests}
    return outputs, timings, records


def _mixed_batch_graph(kind):
    """A graph whose tasks include batch-1 and batched launches: one plan,
    or two admission rounds coalesced."""
    profile = lenet_profile()
    if kind == "plan":
        prob = _uniform_problem(profile, requests=3)
        return compile_plan(_manual_plan(prob, [[3, 4], [3, 4], [1, 4, 2]]))
    prob = _uniform_problem(profile, requests=2)
    return coalesce_graphs([compile_plan(_manual_plan(prob, sizes)) for sizes
                            in ([[3, 4], [1, 4, 2]], [[2, 2, 1, 2], [3, 4]])])


@pytest.mark.parametrize("transport", ["inproc", "loopback"])
@pytest.mark.parametrize("kind", ["plan", "coalesced"])
def test_launch_handoff_matches_row_split(kind, transport):
    """Handing batch-1 outputs on whole and cutting batched ones in one
    dispatch gives the eager per-row split's outputs, launches and transfers
    bit for bit (walls aside), and the loopback run the in-proc run's."""
    graph = _mixed_batch_graph(kind)
    batches = {len(t.requests) for t in graph.tasks}
    assert 1 in batches and max(batches) > 1
    fns = cnn.lenet_layers(cnn.lenet_init(jax.random.PRNGKey(4), 32, 48))
    frames = _frames(np.random.default_rng(5), graph.n_requests, (32, 48, 3))
    inproc = ExecutionEngine(fns).run(graph, frames)
    with make_transport(transport) as tp:
        engine = ExecutionEngine(fns, transport=tp)
        report = engine.run(graph, frames)
        outputs, timings, records = _row_split_run(engine, graph, frames)

    assert report.outputs.keys() == outputs.keys() == inproc.outputs.keys()
    for r, ref in outputs.items():
        got = report.outputs[r]
        assert type(got) is type(ref) and got.dtype == ref.dtype, r
        np.testing.assert_array_equal(got, ref)
        np.testing.assert_array_equal(got, inproc.outputs[r])
    assert [dataclasses.replace(t, wall_s=0.0)
            for t in report.stage_timings] == timings
    assert [dataclasses.replace(t, serialize_s=0.0)
            for t in report.transfers] == records


def test_coalesce_graphs_rejects_model_mismatch():
    lenet = compile_plan(_manual_plan(_uniform_problem(lenet_profile()),
                                      [[3, 4], [3, 4]]))
    vgg = compile_plan(_manual_plan(_uniform_problem(vgg16_profile()),
                                    [[5, 13], [5, 13]]))
    with pytest.raises(ValueError, match="n_layers"):
        coalesce_graphs([lenet, vgg])
    with pytest.raises(ValueError, match="at least one"):
        coalesce_graphs([])
    with pytest.raises(ValueError, match="offsets"):
        coalesce_graphs([lenet], offsets=[0, 2])


def test_calibration_reduces_resolve_mae():
    """The acceptance gate: calibrated profiles cut the predicted-vs-
    measured MAE on a re-solve (analytic FLOP-model error ≫ timing noise)."""
    profile = lenet_profile()
    mob = RPGMobility(RPGParams(n_uavs=8, area_m=150.0, homogeneous=False),
                      seed=0)
    rates = rate_matrix(mob.positions(1)[0], RadioParams())
    rng = np.random.default_rng(0)
    sources = rng.integers(0, 3, 4).astype(np.int64)
    prob = Problem(profile, np.full(8, 128 * MB), np.full(8, 95e9), rates,
                   sources, compute_speed=np.full(8, 9.5e9))
    engine = ExecutionEngine(layer_fns_for(profile, key=jax.random.PRNGKey(0)))
    frames = _frames(rng, 4, (326, 595, 3))
    planner = get_planner("ould-dp")

    plan = planner.plan(prob, SnapshotView(rates))
    graph = compile_plan(plan)
    report = engine.run(graph, frames,
                        predicted_s=np.asarray(plan.evaluate().per_request_s))
    mae_before = report.abs_error_s[list(report.outputs)].mean()

    cal_prob, recon = calibrated_problem(prob, report)
    assert recon.layer_covered.any()
    assert recon.profile.num_layers == profile.num_layers
    # memory/output vectors untouched — calibration only updates compute
    assert recon.profile.memory_vector() == profile.memory_vector()
    assert recon.profile.output_vector() == profile.output_vector()

    replan = planner.plan(cal_prob, SnapshotView(rates))
    regraph = compile_plan(replan)
    rereport = engine.run(
        regraph, frames,
        predicted_s=np.asarray(replan.evaluate().per_request_s))
    mae_after = rereport.abs_error_s[list(rereport.outputs)].mean()
    assert mae_after < mae_before, (mae_before, mae_after)


def test_rejected_requests_never_compiled():
    profile = lenet_profile()
    prob = _uniform_problem(profile, requests=2)
    assign = np.zeros((2, profile.num_layers), np.int64)
    assign[1] = -1
    sol = Solution(assign, 0.0, "rejected:1", 0.0,
                   np.array([True, False]), solver="manual")
    plan = Plan(sol, "manual", "snapshot", prob)
    graph = compile_plan(plan)
    assert graph.requests == (0,)
    assert all(1 not in t.requests for t in graph.tasks)


def test_calibrated_problem_is_new_instance():
    """Calibration never mutates the analytic profile in place."""
    profile = lenet_profile()
    prob = _uniform_problem(profile, requests=1)
    engine = ExecutionEngine(layer_fns_for(profile, key=jax.random.PRNGKey(0)))
    plan = _manual_plan(prob, [[3, 4]])
    report = engine.run(compile_plan(plan),
                        _frames(np.random.default_rng(0), 1, (326, 595, 3)))
    before = list(profile.compute_vector())
    cal_prob, _ = calibrated_problem(prob, report)
    assert profile.compute_vector() == before
    assert cal_prob.profile is not profile
    assert dataclasses.is_dataclass(cal_prob.profile)
