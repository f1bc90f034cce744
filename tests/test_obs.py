"""repro.obs: flight-recorder tracer, metrics registry, and the end-to-end
per-frame latency-breakdown audit (DESIGN.md §9)."""

import dataclasses
import json

import jax
import numpy as np
import pytest

from repro.core import Problem, Solution, lenet_profile
from repro.core.mobility import RPGMobility, RPGParams
from repro.core.planner import Plan
from repro.core.radio import RadioParams, rate_matrix
from repro.exec import ExecutionEngine, compile_plan, layer_fns_for
from repro.obs import (ADMISSION, ENGINE, FRAMES, NULL_TRACER, QUEUE,
                       SOLVER, TRANSPORT, Counter, Gauge, Histogram,
                       MetricsRegistry, NullTracer, Tracer)
from repro.runtime.serve import AdmissionController
from repro.runtime.swarm import SwarmScenario, simulate

MB = 1e6

# S6-style sustained overload, trimmed: one group (queue-driven tails),
# admission uncapped, churn on — every terminal frame fate is reachable.
OVERLOAD = SwarmScenario(
    n_groups=1, duration_ticks=100, epoch_ticks=10, arrival_rate_hz=4.5,
    hold_ticks_mean=240.0, mem_mb_hotspot_group=4096.0,
    mem_mb_other_groups=4096.0, comp_cap_flops=1e18, gflops=5e9,
    deadline_s=2.0, mtbf_s=90.0, mttr_s=30.0)


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------

def test_ring_keeps_latest_and_counts_dropped():
    tr = Tracer(capacity=8)
    for i in range(12):
        tr.span(QUEUE, "s", float(i), 0.5, lane=i, frame=100 + i)
    assert tr.n_events == 8 and tr.n_dropped == 4 and tr.seq == 12
    ev = tr.events()
    np.testing.assert_array_equal(ev["ts"], np.arange(4.0, 12.0))
    np.testing.assert_array_equal(ev["frame"], np.arange(104, 112))
    assert list(ev["name"]) == ["s"] * 8


def test_span_batch_scalar_and_array_operands():
    tr = Tracer(capacity=64)
    ts = np.array([1.0, 2.0, 3.0])
    tr.span_batch(QUEUE, "w", ts, np.array([0.1, 0.2, 0.3]),
                  lane=np.array([5, 6, 7]), frame=np.array([10, 11, 12]),
                  a0=2.5)                       # scalar broadcast: slice fill
    tr.instant_batch(FRAMES, "drop", ts + 9.0, lane=1)
    w = tr.select("w")
    np.testing.assert_allclose(w["dur"], [0.1, 0.2, 0.3])
    np.testing.assert_array_equal(w["lane"], [5, 6, 7])
    np.testing.assert_array_equal(w["frame"], [10, 11, 12])
    np.testing.assert_allclose(w["a0"], 2.5)
    d = tr.select("drop")
    assert (d["dur"] == -1.0).all() and (d["lane"] == 1).all()
    tr.span_batch(QUEUE, "w", np.zeros(0), 0.0)   # empty append is a no-op
    assert tr.n_events == 6


def test_batch_append_wraps_and_oversize_keeps_newest():
    tr = Tracer(capacity=8)
    tr.span_batch(QUEUE, "a", np.arange(5.0), 0.1)      # fills 0..4
    tr.span_batch(QUEUE, "b", np.arange(5.0) + 10, 0.1)  # wraps
    ev = tr.events()                                     # oldest-first
    np.testing.assert_array_equal(ev["ts"], [2, 3, 4, 10, 11, 12, 13, 14])
    assert tr.n_dropped == 2
    big = Tracer(capacity=4)
    big.span_batch(QUEUE, "c", np.arange(100.0), 0.1)   # n >= capacity
    np.testing.assert_array_equal(big.events()["ts"], [96, 97, 98, 99])
    assert big.n_dropped == 96


def test_intern_and_track_registration():
    tr = Tracer(capacity=8)
    assert tr.intern("solve", "n_admitted", "gated") == tr.intern("solve")
    code = tr.track("my_subsystem")             # new subsystem joins here
    assert code == len(("admission", "solver", "queue", "engine",
                        "transport", "frames"))
    assert tr.track("my_subsystem") == code and tr.track("frames") == FRAMES
    tr.span(code, "tick", 0.0, 1.0)
    assert tr.events()["track"][0] == "my_subsystem"


def test_export_chrome_format(tmp_path):
    tr = Tracer(capacity=16)
    tr.intern("solve", "n_admitted", "queue_gated")
    tr.span(SOLVER, "solve", 1.0, 0.25, a0=3.0, a1=1.0,
            args={"cold_dispatch": True})
    tr.instant(ADMISSION, "admit", 1.5, frame=7)
    path = tmp_path / "t.json"
    n = tr.export_chrome(path)
    doc = json.loads(path.read_text())
    assert doc["otherData"]["n_dropped"] == 0
    evs = doc["traceEvents"]
    assert n == len(evs)
    meta = [e for e in evs if e["ph"] == "M"]
    assert {m["args"]["name"] for m in meta if m["name"] == "process_name"} \
        == {"solver", "admission"}
    span = next(e for e in evs if e["ph"] == "X")
    assert span["ts"] == 1.0e6 and span["dur"] == 0.25e6   # microseconds
    assert span["args"] == {"n_admitted": 3.0, "queue_gated": 1.0,
                            "cold_dispatch": True}          # labels + rich
    inst = next(e for e in evs if e["ph"] == "i")
    assert inst["s"] == "t" and inst["args"]["frame"] == 7


def test_null_tracer_is_inert():
    nt = NullTracer()
    assert not nt.enabled and NULL_TRACER.enabled is False
    nt.span(QUEUE, "x", 0.0, 1.0)
    nt.instant(QUEUE, "x", 0.0)
    nt.span_batch(QUEUE, "x", np.arange(3.0), 0.1)
    nt.instant_batch(QUEUE, "x", np.arange(3.0))
    assert nt.n_events == 0 and nt.n_dropped == 0 and nt.now() == 0.0
    assert nt.track("anything") == -1


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_metrics_instruments_and_snapshot():
    m = MetricsRegistry()
    m.counter("sim.served").inc(3)
    m.counter("sim.served").inc()               # same instrument
    m.gauge("solver.total_solve_s").set(1.25)
    h = m.histogram("sim.latency_s", (0.1, 1.0, 10.0))
    h.observe_many(np.array([0.05, 0.5, 0.5, 2.0, 100.0]))
    h.observe(0.5)
    snap = m.snapshot()
    assert snap["sim.served"] == 4
    assert snap["solver.total_solve_s"] == 1.25
    assert snap["sim.latency_s"]["count"] == 6
    assert snap["sim.latency_s"]["counts"] == [1, 3, 1, 1]
    assert h.quantile(0.5) == 1.0               # bucket upper edge
    assert h.quantile(1.0) == float("inf")      # overflow bucket
    assert h.min == 0.05 and h.max == 100.0
    assert m.names() == sorted(snap)


def test_metrics_kind_conflict_and_histogram_edges():
    m = MetricsRegistry()
    m.counter("x")
    with pytest.raises(ValueError, match="already registered"):
        m.gauge("x")
    with pytest.raises(ValueError, match="needs edges"):
        m.histogram("h")
    with pytest.raises(ValueError, match="strictly increasing"):
        Histogram((1.0, 1.0))
    c, g = Counter(), Gauge()
    c.inc(2.5)
    g.set(7)
    assert c.value == 2.5 and g.value == 7


# ---------------------------------------------------------------------------
# end-to-end audit (the satellite acceptance test)
# ---------------------------------------------------------------------------

def test_traced_off_path_bit_identical():
    """Default NullTracer run == untraced run == ring-buffer run."""
    scn = dataclasses.replace(OVERLOAD, duration_ticks=40)
    r0 = simulate(scn, "nearest", seed=3)
    r1 = simulate(scn, "nearest", seed=3, tracer=NullTracer())
    r2 = simulate(scn, "nearest", seed=3, tracer=Tracer(1 << 16))
    for r in (r1, r2):
        assert (r.served, r.missed, r.outages, r.dropped,
                r.frames_rejected) == (r0.served, r0.missed, r0.outages,
                                       r0.dropped, r0.frames_rejected)
        np.testing.assert_array_equal(r.latencies, r0.latencies)
    assert r0.metrics["sim.served"] == r0.served     # registry agrees too


@pytest.mark.parametrize("policy,fate", [("edf+drop", "dropped"),
                                         ("fifo+reject", "frames_rejected")])
def test_latency_breakdown_audit(policy, fate):
    """Span algebra ``frame.dur == base + wait + service`` for every
    completion, and event conservation vs SimResult: every served frame
    ends as exactly one of outage / completion span / drop / reject.
    Bottleneck mode — the per-hop twin audits the tandem spans below."""
    scn = dataclasses.replace(OVERLOAD, service_policy=policy,
                              queue_model="bottleneck")
    tr = Tracer(1 << 18)
    r = simulate(scn, "nearest", seed=1, tracer=tr)
    assert getattr(r, fate) > 0 and r.outages > 0    # the fates all occur
    assert tr.n_dropped == 0                         # ring held everything

    f, w, s = tr.select("frame"), tr.select("queue_wait"), tr.select("service")
    # batch appends preserve emission order: the three span families align
    np.testing.assert_array_equal(f["frame"], w["frame"])
    np.testing.assert_array_equal(f["frame"], s["frame"])
    np.testing.assert_allclose(f["dur"], f["a0"] + w["dur"] + s["dur"],
                               atol=1e-9)
    assert f["ts"].size == r.latencies.size
    np.testing.assert_allclose(np.sort(f["dur"]), np.sort(r.latencies))

    n_drop = tr.select("drop")["ts"].size
    n_rej = tr.select("reject_queue")["ts"].size
    n_out = tr.select("outage")["ts"].size
    assert n_out == r.outages and n_drop == r.dropped
    assert n_rej == r.frames_rejected
    assert r.served == n_out + f["ts"].size + n_drop + n_rej

    # the registry snapshot mirrors the same totals
    assert r.metrics["sim.served"] == r.served
    assert r.metrics["queue.dropped"] == r.dropped
    assert r.metrics["sim.latency_s"]["count"] == r.latencies.size


def test_perhop_latency_breakdown_audit():
    """Per-hop event conservation (the tandem-network twin of the audit
    above): every completed frame's duration decomposes into its hop
    spans — ``frame.dur == Σ hop_wait + Σ hop_service + Σ link`` grouped
    per frame id — and the fate counts still conserve vs SimResult."""
    tr = Tracer(1 << 19)
    r = simulate(OVERLOAD, "nearest", seed=1, tracer=tr)
    assert tr.n_dropped == 0
    f = tr.select("frame")
    assert f["ts"].size == r.latencies.size
    np.testing.assert_allclose(np.sort(f["dur"]), np.sort(r.latencies))
    # a0/a1 carry the wait/work split: they must re-sum to the duration
    np.testing.assert_allclose(f["dur"], f["a0"] + f["a1"], atol=1e-9)

    # A stream serves one frame per tick, so frame ids repeat across
    # windows — conservation is audited per *stream*: the summed hop spans
    # of each id must equal its summed frame durations.
    hops: dict[int, float] = {}
    for name in ("hop_wait", "hop_service", "link"):
        ev = tr.select(name)
        assert ev["ts"].size > 0                 # all three families emitted
        for fr, dur in zip(ev["frame"], ev["dur"]):
            hops[int(fr)] = hops.get(int(fr), 0.0) + float(dur)
    frames: dict[int, float] = {}
    for fr, dur in zip(f["frame"], f["dur"]):
        frames[int(fr)] = frames.get(int(fr), 0.0) + float(dur)
    assert set(hops) == set(frames)
    for fr, tot in frames.items():
        assert hops[fr] == pytest.approx(tot, abs=1e-6)

    n_out = tr.select("outage")["ts"].size
    assert n_out == r.outages
    assert r.served == n_out + f["ts"].size + r.dropped + r.frames_rejected


def test_trace_carries_churn_and_epoch_solves(tmp_path):
    scn = dataclasses.replace(OVERLOAD, duration_ticks=60)
    tr = Tracer(1 << 17)
    r = simulate(scn, "incremental", seed=2, tracer=tr)
    solves = tr.select("solve")
    assert solves["ts"].size >= 1                # epoch re-solves traced
    assert (tr.select("node_fail")["ts"].size
            + tr.select("node_rejoin")["ts"].size) > 0
    assert tr.select("arrival")["ts"].size == r.metrics["sim.arrivals"]
    # exported trace is valid Chrome JSON with the churn track registered
    path = tmp_path / "swarm.json"
    tr.export_chrome(path)
    doc = json.loads(path.read_text())
    names = {e["args"]["name"] for e in doc["traceEvents"]
             if e.get("name") == "process_name"}
    assert {"solver", "queue", "frames", "churn"} <= names


# ---------------------------------------------------------------------------
# solver spans: cold-dispatch flag (the ResolveStats wall-time fix)
# ---------------------------------------------------------------------------

def _pool_problem(n_nodes=23, requests=6, seed=0):
    mob = RPGMobility(RPGParams(n_uavs=n_nodes, area_m=150.0,
                                homogeneous=False), seed=seed)
    rates = rate_matrix(mob.positions(1, seed=seed)[0], RadioParams())
    src = (np.arange(requests) % 3).astype(np.int64)
    return Problem(lenet_profile(), np.full(n_nodes, 4096 * MB),
                   np.full(n_nodes, 1e18), rates, src,
                   compute_speed=np.full(n_nodes, 9.5e9))


def test_cold_dispatch_flag_separates_compile_from_solve():
    """A batched-DP solve that triggered XLA compilation flags its stats;
    the identical re-solve does not — so solve_time_s is only read as
    steady-state cost when cold_dispatch is False."""
    prob = _pool_problem()       # unusual shape ⇒ compiles within this test
    tr = Tracer(1 << 12)
    ctrl = AdmissionController("ould-dp-sparse", tracer=tr, batch_solve=True)
    ids = list(range(prob.n_requests))
    p1 = ctrl.admit(prob, prob.rates, request_ids=ids, now_s=0.0)
    p2 = ctrl.admit(prob, prob.rates, request_ids=ids, now_s=1.0)
    s1, s2 = p1.solve_stats, p2.solve_stats
    assert s1.n_batched > 0
    assert s2.n_jit_compiles == 0 and not s2.cold_dispatch
    assert s1.n_jit_compiles >= s2.n_jit_compiles
    # both rounds traced: solver spans carry the flag in their rich args
    ev = tr.events()
    solver_rich = [tr._rich[k] for k in sorted(tr._rich)
                   if "cold_dispatch" in tr._rich[k]]
    assert len(solver_rich) == 2
    assert solver_rich[1]["cold_dispatch"] is False
    assert ev["name"].tolist().count("solve") == 2
    # per-request admission verdict instants cover the whole batch
    n_adm = tr.select("admit")["ts"].size
    n_rej = tr.select("reject")["ts"].size
    assert n_adm + n_rej == 2 * len(ids)


# ---------------------------------------------------------------------------
# engine + transport spans
# ---------------------------------------------------------------------------

def _two_stage_run(tracer=None, cuts=([3, 4], [3, 4])):
    """LeNet with request r's stage s on node s, ``cuts[r]`` its stage
    sizes.  By default two requests share both stages over two nodes
    (layers cross a link): one batched launch per stage and one transfer
    each."""
    profile = lenet_profile()
    R = len(cuts)
    prob = _pool_problem(n_nodes=6, requests=R)
    assign = np.stack([np.repeat(np.arange(len(c)), c) for c in cuts])
    sol = Solution(assign, 0.0, "feasible", 0.0, np.ones(R, bool),
                   solver="manual")
    graph = compile_plan(Plan(sol, "manual", "snapshot", prob))
    engine = ExecutionEngine(layer_fns_for(profile, key=jax.random.PRNGKey(0)),
                             tracer=tracer)
    frames = np.random.default_rng(0).standard_normal(
        (R, 326, 595, 3)).astype(np.float32)
    return graph, engine.run(graph, frames)


def test_engine_and_transport_spans():
    """Live spans: one ``launch`` per task with one ``dispatch`` inside it,
    launch walls equal to ``StageTiming.wall_s`` and ship walls equal to
    ``serialize_s`` (one clock reading each), bytes accounted exactly, one
    ``done`` per request, and every event inside the one ``run``."""
    tr = Tracer(1 << 12)
    graph, report = _two_stage_run(tr)
    run = tr.select("run")
    assert run["ts"].size == 1
    lo, hi = run["ts"][0], run["ts"][0] + run["dur"][0]
    assert (run["a0"][0], run["a1"][0]) == (len(graph.requests),
                                            len(graph.tasks))

    launch, dispatch = tr.select("launch"), tr.select("dispatch")
    assert launch["ts"].size == dispatch["ts"].size == len(graph.tasks)
    np.testing.assert_array_equal(
        launch["dur"], [t.wall_s for t in report.stage_timings])
    np.testing.assert_array_equal(launch["a0"],
                                  [len(t.requests) for t in graph.tasks])
    np.testing.assert_array_equal(launch["lane"],
                                  [t.node for t in graph.tasks])
    eps = 1e-9
    assert (dispatch["ts"] >= launch["ts"] - eps).all()
    assert (dispatch["ts"] + dispatch["dur"]
            <= launch["ts"] + launch["dur"] + eps).all()

    ships = tr.select("ship")
    assert ships["ts"].size == len(graph.transfers)
    np.testing.assert_array_equal(
        ships["dur"], [t.serialize_s for t in report.transfers])
    # a0 = realized bytes per shipment (batched shared stages ship once for
    # all requests, so realized >= the per-request modeled boundary bytes)
    assert ships["a0"].min() > 0
    assert ships["a0"].sum() >= max(t.nbytes for t in graph.transfers)

    done = tr.select("done")
    assert sorted(done["frame"]) == sorted(graph.requests)
    assert (done["dur"] == -1.0).all()

    ev = tr.events()
    assert set(ev["track"]) == {"engine", "transport"}
    inner = ev["name"] != "run"
    end = ev["ts"] + np.maximum(ev["dur"], 0.0)
    assert (ev["ts"][inner] >= lo - eps).all()
    assert (end[inner] <= hi + eps).all()
    # the first run compiles every shape, each outside its launch
    assert tr.select("compile")["ts"].size == len(graph.tasks)
    fetch = tr.select("fetch")
    assert fetch["a0"][0] == sum(o.nbytes for o in report.outputs.values())


@pytest.mark.parametrize("cuts", [
    ([3, 4], [3, 4]),                 # batched tasks only
    ([3, 4], [1, 4, 2]),              # batch-1 tasks only
    ([3, 4], [3, 4], [1, 4, 2]),      # both
])
def test_split_spans_count_rows_and_copies(cuts):
    """One ``split`` per task: a0 = its batch, a1 = the device dispatches
    the hand-off made (0 for a batch-1 output passed on whole, 1 for a
    batched one cut into rows); the ``fetch`` carries every answer's
    bytes."""
    tr = Tracer(1 << 12)
    graph, report = _two_stage_run(tr, cuts)

    split = tr.select("split")
    batches = [len(t.requests) for t in graph.tasks]
    np.testing.assert_array_equal(split["a0"], batches)
    np.testing.assert_array_equal(split["a1"], [int(b > 1) for b in batches])
    (fetch_bytes,) = tr.select("fetch")["a0"]
    assert fetch_bytes == len(graph.requests) * 10 * 4     # f32 logits
    assert fetch_bytes == sum(o.nbytes for o in report.outputs.values())


def test_scope_lands_in_profiler_trace(tmp_path):
    """``Tracer.scope`` writes ``repro.<track>.<name>`` host events into a
    ``jax.profiler`` trace, each enclosing its ring span's interval."""
    import glob

    import jax.numpy as jnp

    tr = Tracer(64)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with tr.scope(ENGINE, "run") as span:
            with tr.scope(TRANSPORT, "ship", lane=2):
                jnp.ones(8).block_until_ready()
            span.set(a0=4.0)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    host = {e.name: e for p in pd.planes for ln in p.lines
            for e in ln.events if e.name.startswith("repro.")}
    assert set(host) == {"repro.engine.run", "repro.transport.ship"}
    run, ship = host["repro.engine.run"], host["repro.transport.ship"]
    assert run.start_ns <= ship.start_ns
    assert ship.start_ns + ship.duration_ns <= run.start_ns + run.duration_ns
    for name, ev in (("run", run), ("ship", ship)):
        assert tr.select(name)["dur"][0] <= ev.duration_ns * 1e-9 + 1e-6
    assert tr.select("run")["a0"][0] == 4.0
    assert tr.select("ship")["lane"][0] == 2


def test_null_scope_is_inert_and_tracing_changes_no_output():
    """``NullTracer.scope`` is one shared no-op; an engine run with a live
    tracer gives the very outputs of a run without one."""
    nt = NullTracer()
    with nt.scope(ENGINE, "run", a0=1.0) as span:
        span.set(a0=2.0, args={"x": 1})
        span.interval(0.0, 1.0)
    assert nt.scope(ENGINE, "run") is nt.scope(QUEUE, "other", lane=3)
    assert nt.n_events == 0 and nt.events()["ts"].size == 0

    _, plain = _two_stage_run()
    tr = Tracer(1 << 12)
    _, traced = _two_stage_run(tr)
    assert tr.n_events > 0
    assert plain.outputs.keys() == traced.outputs.keys()
    for r, out in plain.outputs.items():
        assert out.dtype == traced.outputs[r].dtype
        np.testing.assert_array_equal(out, traced.outputs[r])


def test_live_admission_spans():
    """Real-time admission (no ``now_s``): one live ``admit`` span holding
    the planner call's live ``solve`` span, with the verdicts and the
    ResolveStats args, plus one verdict instant per request."""
    prob = _pool_problem()
    tr = Tracer(1 << 12)
    ctrl = AdmissionController("ould-dp-sparse", tracer=tr)
    ids = list(range(prob.n_requests))
    plan = ctrl.admit(prob, prob.rates, request_ids=ids)
    ev = tr.events()
    spans = ev["dur"] >= 0
    admit = spans & (ev["name"] == "admit")
    solve = spans & (ev["name"] == "solve")
    assert admit.sum() == 1 and solve.sum() == 1
    a, s = int(np.flatnonzero(admit)[0]), int(np.flatnonzero(solve)[0])
    assert ev["ts"][a] <= ev["ts"][s]
    assert ev["ts"][s] + ev["dur"][s] <= ev["ts"][a] + ev["dur"][a] + 1e-9
    assert ev["a0"][a] == plan.n_admitted and ev["a1"][a] == 0
    base = tr.seq - tr.n_events
    assert tr._rich[base + a] == {"n_admitted": int(plan.n_admitted),
                                  "queue_gated": 0}
    assert "cold_dispatch" in tr._rich[base + s]
    n_verdicts = ((ev["dur"] == -1.0)
                  & np.isin(ev["name"], ["admit", "reject"])).sum()
    assert n_verdicts == len(ids)


def test_executed_swarm_trace_stays_in_simulated_time():
    """An executed swarm run measures stage walls on the wall clock; none
    of those measurements reaches its simulated-time trace."""
    scn = dataclasses.replace(OVERLOAD, duration_ticks=20, execute=True)
    tr = Tracer(1 << 16)
    r = simulate(scn, "nearest", seed=1, tracer=tr)
    assert r.served > 0 and tr.n_events > 0
    ev = tr.events()
    assert not {"engine", "transport"} & set(ev["track"])
    assert not {"stage_measure", "warm_start", "ship"} & set(ev["name"])
