"""Closed-loop camera rounds on the served path.

A fixed swarm (one RPG snapshot, from the traffic file's topology seed)
carries ``streams`` persistent camera streams sourced at its first
``hotspots`` UAVs.  Each round every stream submits one frame; the round
is admitted and placed (``AdmissionController.admit``), compiled to a stage
graph (``compile_plan``) and executed (``ExecutionEngine.run`` on the
in-process transport).  The next round starts when the last output is on
the host.  A frame's latency runs from its round's start to its output on
the host.  ``--seed`` draws the weights and the frames, never the
deployment, so every seed runs the same plan on the same shapes; every
round sends the same frames, whose content the served path never looks at
beyond computing on it.

Correctness covers what the window produced, at its sizes, along the whole
chain of one window round drawn from the seed:

* ``launch_gap``: every stage launch of the round is recomputed by the
  configuration's plain reference, at the stated precision, from the very
  input the launch was given, and the widest relative gap
  ``max|y - ref| / max|ref|`` over the launches is compared.  Launch by
  launch, because in float32 at JAX's default precision (bf16 passes on the
  TPU) rounding differences grow from layer to layer to the size of a
  bfloat16 computation's own error, so only a short chain of layers can
  tell the stated precision from a lower one;
* ``chain_breaks``: the inputs of those launches are held to what produced
  them, bit for bit: a request's first launch reads its frame, every later
  one reads the row that the request's previous launch output, as the
  transfers between them delivered it, and its answer is the row of its
  last launch.  So a transfer that narrows, corrupts or misroutes an
  activation breaks the chain even where it does so in every round;
* ``bad_answers``: answers that are missing, misshapen or not finite, that
  differ by a bit from the checked round's answer for the same frame, or
  that lie nearer another frame's reference output than their own.
"""

from __future__ import annotations

import importlib
import time

import numpy as np

from harness import costs, runtime, swarm


def deployment(cfg: dict, tr: dict):
    """The placement instance: profile from the benchmark's own counts,
    radio rates of the fixed snapshot, hotspot sources."""
    from repro.core import Problem

    n = tr["uavs"]
    pos = swarm.rpg_positions(n, tr["area_m"], 1, tr["topology_seed"],
                              homogeneous=True)[0]
    sources = swarm.hotspot_sources(tr["streams"], tr["hotspots"], n,
                                    tr["topology_seed"])
    return Problem(costs.program_profile(cfg),
                   np.full(n, tr["node_mem_bytes"]),
                   np.full(n, tr["node_comp_flops"]), swarm.rate_matrix(pos),
                   sources, compute_speed=np.full(n, tr["node_flops_per_s"]))


def program_layers(cfg: dict, params: dict):
    mod, attr = cfg["program"]["layers"].split(":")
    return getattr(importlib.import_module(mod), attr)(params)


class LaunchRecorder:
    """Wraps ``engine.closure`` so that, while ``on``, every launch's range,
    input and output (device arrays, no copy) are kept for the check."""

    def __init__(self, engine):
        self.on = False
        self.launches: list[tuple[int, int, object, object]] = []
        closure = engine.closure

        def recording(start, end):
            fn = closure(start, end)

            def run(x):
                y = fn(x)
                if self.on:
                    self.launches.append((start, end, x, y))
                return y
            return run
        engine.closure = recording


def run(ctx) -> tuple[dict, dict]:
    from repro.core import SnapshotView
    from repro.exec import ExecutionEngine, compile_plan
    from repro.runtime.serve import AdmissionController

    cfg, tr = ctx.config, ctx.traffic
    ref = runtime.reference(cfg["reference"])
    S = tr["streams"]
    prob = deployment(cfg, tr)
    view = SnapshotView(prob.rates)
    params = ref.init(cfg, ctx.seed)
    engine = ExecutionEngine(program_layers(cfg, params))
    recorder = LaunchRecorder(engine)
    if ctx.tracing:
        ship = engine.transport.ship

        def traced_ship(*a, **k):
            with ctx.spans("transfer"):
                return ship(*a, **k)
        engine.transport.ship = traced_ship
    admission = AdmissionController(tr["planner"])
    frames = swarm.frames(S, cfg["frame"], ctx.seed)
    ids = list(range(S))
    spans = ctx.spans

    def one_round():
        with spans("admit"):
            plan = admission.admit(prob, view, request_ids=ids)
        with spans("compile_plan"):
            graph = compile_plan(plan)
        with spans("run"):
            report = engine.run(graph, frames)
        return plan, graph, report

    for _ in range(tr["warmup_rounds"]):
        plan, graph, report = one_round()
    runtime.log(
        f"[plan] admitted={plan.n_admitted}/{S} launches={len(graph.tasks)} "
        f"transfers={len(graph.transfers)} transfer_bytes_per_frame="
        f"{sum(t.nbytes for t in graph.transfers) / S:.0f} closures="
        f"{len({(t.layer_start, t.layer_end, len(t.requests)) for t in graph.tasks})} "
        f"nodes_per_frame={[len(set(plan.assign[r].tolist())) for r in ids]}")
    ctx.setup_done()

    checked = ctx.seed % tr["check_round_span"]
    checked_tasks = []
    rounds = []
    cost_of: dict = {}
    pk = ctx.peaks
    with ctx.window():
        t0 = time.perf_counter()
        deadline = t0 + ctx.seconds
        while time.perf_counter() < deadline:
            recorder.on = len(rounds) == checked
            ts = time.perf_counter()
            plan, graph, report = one_round()
            te = time.perf_counter()
            if recorder.on:
                checked_tasks = graph.tasks
            sig = tuple((t.layer_start, t.layer_end, len(t.requests))
                        for t in graph.tasks)
            if sig not in cost_of:
                cost_of[sig] = (
                    sum(costs.conv_bound_s(cfg, s, e, b, pk["flops_per_s"],
                                           pk["hbm_bytes_per_s"])
                        for s, e, b in sig),
                    sum(b * costs.range_flops(cfg, s, e) for s, e, b in sig))
            rounds.append(dict(
                start=ts, end=te, outputs=report.outputs,
                admitted=int(plan.n_admitted), launches=len(graph.tasks),
                transfers=len(graph.transfers),
                stage_s=sum(t.wall_s for t in report.stage_timings),
                transfer_s=sum(t.serialize_s for t in report.transfers),
                conv_bound_s=cost_of[sig][0], launch_flops=cost_of[sig][1]))
        t_end = time.perf_counter()
        recorder.on = False
    ctx.window_closed()
    runtime.log(f"[window] rounds={len(rounds)} plans_seen={len(cost_of)} "
                f"launches_per_round={sorted({r['launches'] for r in rounds})} "
                f"transfers_per_round={sorted({r['transfers'] for r in rounds})}"
                f" checked_round={checked}")

    # Free the program's state before the reference runs.
    launches = [(s, e, t.requests, x, y) for (s, e, x, y), t
                in zip(recorder.launches, checked_tasks)]
    if len(recorder.launches) != len(checked_tasks):
        launches = []               # not one launch per task: nothing to hold
    del engine, recorder, admission, plan, graph, report, one_round
    import jax
    jax.clear_caches()

    answers = [r["outputs"] for r in rounds]
    missing = sum(r["admitted"] - len(r["outputs"]) for r in rounds)
    readings = compare(cfg, ref, params, frames, launches, answers,
                       checked, missing)
    done = sum(len(a) for a in answers)
    rec = ctx.record(
        window_s=t_end - t0, frames_done=done,
        frame_latencies_s=[r["end"] - r["start"] for r in rounds
                           for _ in r["outputs"]],
        stage_s=sum(r["stage_s"] for r in rounds),
        transfer_s=sum(r["transfer_s"] for r in rounds),
        conv_bound_s=sum(r["conv_bound_s"] for r in rounds),
        launch_flops=sum(r["launch_flops"] for r in rounds),
        attempted=S * len(rounds), failed=S * len(rounds) - done)
    return rec, readings


def compare(cfg, ref, params, frames, launches, answers, checked,
            missing=0, dtype="float32") -> dict:
    """``launch_gap`` and ``chain_breaks`` over the checked round's launches
    ``(start, end, requests, x, y)``, in the order they ran, and the count
    of ``bad_answers`` over every round (see the module docstring)."""
    t0 = time.perf_counter()
    n_units = len(cfg["units"])
    gap = 0.0
    breaks = 0
    held = {}                       # request -> (unit, its activation row)
    for s, e, reqs, x, y in launches:
        x = np.asarray(x)
        for b, r in enumerate(reqs):
            unit, row = held.get(r, (0, frames[r]))
            if unit != s or not _same(x[b], row):
                breaks += 1
        want = ref.forward(cfg, params, x, dtype, s, e)
        got = np.asarray(y)
        if got.shape != want.shape or not np.isfinite(got).all():
            gap = float("inf")
        else:
            gap = max(gap, _rel_gap(got, want))
        for b, r in enumerate(reqs):
            held[r] = (e, got[b])
    bad = missing
    if checked >= len(answers) or not launches:
        bad += 1                    # the window never reached the checked round
        truth = {}
    else:
        truth = answers[checked]
    want = ref.forward(cfg, params, frames, dtype)
    e2e = 0.0
    for q, out in truth.items():
        unit, row = held.get(q, (0, None))
        if unit != n_units or not _same(out, row):
            breaks += 1
        out = np.asarray(out, np.float32)
        if out.shape != want[q].shape or not np.isfinite(out).all():
            bad += 1
            continue
        dist = [np.abs(out - w).max() for w in want]
        e2e = max(e2e, dist[q] / np.abs(want[q]).max())
        if int(np.argmin(dist)) != q:
            bad += 1                # nearer another frame's answer
    for outs in answers:
        for q, out in outs.items():
            if q not in truth or not _same(out, truth[q]):
                bad += 1
    runtime.log(f"[check] {len(launches)} launches of round {checked} and "
                f"{sum(map(len, answers))} answers compared in "
                f"{time.perf_counter() - t0:.3f}s; end-to-end answer gap "
                f"to the reference from the frames (not compared, the "
                f"noise of the stated precision): {float(e2e)!r}")
    return {"launch_gap": gap, "chain_breaks": breaks, "bad_answers": bad}


def _rel_gap(got, want) -> float:
    """``max|got - want| / max|want|``; an all-zero ``want`` gives 0 where
    ``got`` equals it and infinity elsewhere."""
    num = float(np.abs(got.astype(np.float32) - want).max())
    den = float(np.abs(want).max())
    return num / den if den > 0 else (0.0 if num == 0 else float("inf"))


def _same(a, b) -> bool:
    """Bit for bit: the same dtype, shape and values."""
    if b is None:
        return False
    a, b = np.asarray(a), np.asarray(b)
    return a.dtype == b.dtype and np.array_equal(a, b)


def control(cfg: dict, tr: dict, seed: int) -> dict:
    """The readings of the lower-precision control: the reference itself,
    recomputed in bfloat16, put in the program's place for every launch of
    the cell's plan (the plan from the program's own planner), fed as the
    engine feeds its launches."""
    from repro.core import SnapshotView
    from repro.exec import compile_plan
    from repro.runtime.serve import AdmissionController

    ref = runtime.reference(cfg["reference"])
    S = tr["streams"]
    prob = deployment(cfg, tr)
    plan = AdmissionController(tr["planner"]).admit(
        prob, SnapshotView(prob.rates), request_ids=list(range(S)))
    graph = compile_plan(plan)
    params = ref.init(cfg, seed)
    frames = swarm.frames(S, cfg["frame"], seed)
    acts = {r: frames[r][None] for r in graph.requests}
    launches = []
    for t in graph.tasks:
        x = np.concatenate([acts[r] for r in t.requests])
        y = ref.forward(cfg, params, x, "bfloat16", t.layer_start,
                        t.layer_end)
        launches.append((t.layer_start, t.layer_end, t.requests, x, y))
        for b, r in enumerate(t.requests):
            acts[r] = y[b:b + 1]
    answers = [{r: acts[r][0] for r in graph.requests}]
    return compare(cfg, ref, params, frames, launches, answers, 0)
