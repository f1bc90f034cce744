"""Serving launcher: batched greedy generation with the production server
(prefill + donated-cache decode), reduced config by default or the full
config at its production dtypes with ``--full-width`` — plus request
placement over the serving pool via any registered planner.

    PYTHONPATH=src python -m repro.launch.serve --arch yi_6b --steps 16 \\
        --planner ould-dp --pool-nodes 8

The persistent compile cache is always on (``repro.exec.compile_cache``:
``JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``).
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
from typing import Any

import numpy as np

# Per-node memory of the executed CNN pool, sized from the profile.  LeNet
# (~108 MB) at 128 MB: co-sourced requests must offload part of their path.
# VGG16 (~1.0 GB, its head unit alone ~480 MB) at the paper's high memory
# level, 512 MB: every request splits over several nodes.  ViT-B/16 (~410
# MB, 12.2 GFLOP a block at 741 tokens) at the same level splits by
# compute: its 147 GFLOP exceed a node's 95 GFLOP.
NODE_MEM_BYTES = {"lenet": 128e6, "vgg16": 512e6, "vitb16": 512e6}


@dataclasses.dataclass
class ServeRun:
    """What one :func:`main` call served, for scripts that check it."""

    server: Any                   # runtime.serve.Server
    prompts: np.ndarray           # (batch, prompt_len) token ids
    generated: np.ndarray         # (batch, steps) greedy token ids
    # --execute only: the placed CNN round and its engine
    engine: Any = None
    frames: np.ndarray | None = None
    cnn_plan: Any = None
    graph: Any = None
    report: Any = None


def main(argv: list[str] | None = None) -> ServeRun:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2_1p8b")
    ap.add_argument("--full-width", action="store_true",
                    help="serve the config at full size with bf16 params "
                         "and compute (default: a 2-layer, d_model=128 "
                         "reduction in f32)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--planner", default="ould-dp",
                    help="registered placement strategy for the pool "
                         "(see repro.core.available_planners())")
    ap.add_argument("--pool-nodes", type=int, default=8)
    ap.add_argument("--sparse-k", type=int, default=None,
                    help="candidate budget for the *-sparse planners "
                         "(default: ceil(sqrt(pool nodes)))")
    ap.add_argument("--execute", action="store_true",
                    help="run a placed CNN inference through the repro.exec "
                         "engine and report predicted vs measured latency "
                         "(plus a calibrated re-solve)")
    ap.add_argument("--model", default="lenet", choices=sorted(NODE_MEM_BYTES),
                    help="model placed and executed under --execute")
    ap.add_argument("--transport", default="inproc",
                    choices=("inproc", "loopback", "multiproc"),
                    help="byte-moving backend for --execute transfers: "
                         "inproc = modeled delay (default), loopback = "
                         "worker OS processes over sockets, multiproc = one "
                         "JAX process per node group; non-inproc backends "
                         "also calibrate the rates from realized bandwidth "
                         "before the re-solve")
    ap.add_argument("--transport-workers", type=int, default=2)
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome/Perfetto-loadable trace of this "
                         "run (repro.obs): solver/admission spans for the "
                         "pool placement; under --execute, live engine.run "
                         "spans with their launches and transport "
                         "shipments")
    args = ap.parse_args(argv)

    tracer = metrics = None
    if args.trace_out:
        from repro.obs import MetricsRegistry, Tracer
        tracer = Tracer()
        metrics = MetricsRegistry()

    import jax

    import repro.configs as C
    from repro.core.radio import TpuLinkModel
    from repro.exec import compile_cache
    from repro.models import init_params
    from repro.runtime.serve import ServeConfig, Server, schedule_requests

    compile_cache.enable()
    cfg = C.get_config(args.arch)
    cfg = (cfg.production() if args.full_width
           else cfg.reduced(n_layers=2, d_model=128, vocab=1024))
    params = jax.jit(functools.partial(init_params, cfg=cfg))(
        jax.random.PRNGKey(0))
    srv = Server(cfg, params, ServeConfig(
        max_len=args.prompt_len + args.steps + 1, batch_size=args.batch))
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab, (args.batch, args.prompt_len), dtype=np.int32)
    out = srv.generate(prompts, steps=args.steps)
    print(f"[serve] arch={args.arch} generated {out.shape}: {out[0].tolist()}")

    # Place the batch's requests over a simulated pool with the chosen
    # planner — provenance comes from the Plan, not a hard-coded label.
    link = TpuLinkModel()
    n = args.pool_nodes
    coords = np.stack([np.arange(n) % link.torus[0],
                       np.arange(n) // link.torus[0]], -1)
    rates_bits = link.rate_matrix(coords, np.zeros(n, np.int64)) * 8.0
    plan, ev = schedule_requests(
        C.get_config(args.arch), n_nodes=n, requests=args.batch,
        hbm_bytes=16e9 * 16, flops_budget=197e12 * 10,
        rates_bits=rates_bits, planner=args.planner,
        sparse_k=args.sparse_k)
    sparse = ""
    if plan.solve_stats is not None and plan.solve_stats.k:
        st = plan.solve_stats
        sparse = (f" sparse[k={st.k} pruned={st.pruned_fraction:.2f} "
                  f"dense_fallbacks={st.n_dense_fallback}]")
    print(f"[serve] placement planner={plan.planner_name} "
          f"view={plan.view_kind} status={plan.status} "
          f"admitted={plan.n_admitted}/{args.batch} "
          f"comm={ev.comm_latency_s * 1e6:.1f}us "
          f"stages(req0)={len(plan.stages(0)) if plan.admitted[0] else 0}"
          + sparse)
    run = ServeRun(srv, prompts, out)

    if args.execute:
        # Plan-faithful execution: place the paper's CNN over the same pool
        # with the same planner, run it through the exec engine (transfers
        # routed through the chosen transport backend), then re-solve on the
        # measured-calibrated profile — and, with a byte-moving transport,
        # on realized link bandwidth too (DESIGN.md §5/§7).
        from repro.core import (Problem, SnapshotView, get_planner,
                                lenet_profile, vgg16_profile, vitb16_profile)
        from repro.exec import (ExecutionEngine, calibrated_problem,
                                compile_plan, layer_fns_for)
        from repro.transport import make_transport

        profile = {"lenet": lenet_profile, "vgg16": vgg16_profile,
                   "vitb16": vitb16_profile}[args.model]()
        rng = np.random.default_rng(0)
        # Hotspot the frames on two camera nodes; at NODE_MEM_BYTES the plan
        # has transfers for the transport to carry.
        sources = (np.arange(args.batch) % min(2, n)).astype(np.int64)
        prob = Problem(profile, np.full(n, NODE_MEM_BYTES[args.model]),
                       np.full(n, 95e9), rates_bits, sources,
                       compute_speed=np.full(n, 9.5e9))
        if tracer is not None:
            # Route placement through the controller so the trace carries
            # the solver span + per-request admission verdicts.
            from repro.runtime.serve import AdmissionController
            cnn_plan = AdmissionController(
                args.planner, tracer=tracer, sparse_k=args.sparse_k).admit(
                prob, SnapshotView(rates_bits),
                request_ids=list(range(args.batch)))
        else:
            cnn_plan = get_planner(args.planner, sparse_k=args.sparse_k).plan(
                prob, SnapshotView(rates_bits))
        graph = compile_plan(cnn_plan)
        transport = make_transport(args.transport,
                                   n_workers=args.transport_workers)
        engine = ExecutionEngine(layer_fns_for(profile), transport=transport,
                                 tracer=tracer)
        frames = rng.standard_normal(
            (args.batch, 326, 595, 3)).astype(np.float32)
        try:
            report = engine.run(graph, frames,
                                predicted_s=cnn_plan.evaluate().per_request_s)
            moving = args.transport != "inproc"
            cal_prob, recon = calibrated_problem(
                prob, report, transport=transport if moving else None)
            replan = get_planner(args.planner, sparse_k=args.sparse_k).plan(
                cal_prob, SnapshotView(cal_prob.rates))
            regraph = compile_plan(replan)
            rereport = engine.run(regraph, frames,
                                  predicted_s=replan.evaluate().per_request_s)
        finally:
            transport.close()
        run.engine, run.frames, run.cnn_plan = engine, frames, cnn_plan
        run.graph, run.report = graph, report
        mae0 = report.abs_error_s[list(report.outputs)].mean()
        mae1 = rereport.abs_error_s[list(rereport.outputs)].mean()
        print(f"[exec] model={args.model} admitted={cnn_plan.n_admitted}/"
              f"{args.batch} tasks={len(graph.tasks)} shared={graph.n_shared} "
              f"transfers={len(graph.transfers)} "
              f"executed_avg={report.executed_s[list(report.outputs)].mean():.4f}s")
        print(f"[exec] {recon.summary()}")
        if args.transport != "inproc":
            bw = ", ".join(
                f"{s}->{d}: {ls.bytes_per_s / 1e6:.0f} MB/s"
                for (s, d), ls in sorted(transport.link_stats.items()))
            print(f"[exec] transport={args.transport} "
                  f"workers={sorted(set(transport.worker_pids))} "
                  f"moved={transport.moved_bytes / 1e6:.1f}MB ({bw})")
            print(f"[exec] re-solve priced comm from "
                  f"{replan.problem.comm_source!r}")
        print(f"[exec] predicted-vs-measured MAE {mae0 * 1e3:.2f}ms -> "
              f"{mae1 * 1e3:.2f}ms after calibrated re-solve")
        if metrics is not None:
            metrics.counter("exec.tasks").inc(len(graph.tasks))
            metrics.counter("exec.transfers").inc(len(graph.transfers))
            metrics.counter("exec.admitted").inc(int(cnn_plan.n_admitted))
            metrics.gauge("exec.executed_avg_s").set(
                float(report.executed_s[list(report.outputs)].mean()))
            metrics.gauge("exec.mae_s").set(float(mae0))
            metrics.gauge("exec.mae_recal_s").set(float(mae1))
            for (s, d), ls in sorted(transport.link_stats.items()):
                metrics.gauge(f"transport.link.{s}-{d}.bytes_per_s").set(
                    ls.bytes_per_s)

    if tracer is not None:
        n_ev = tracer.export_chrome(args.trace_out)
        print(f"[trace] wrote {n_ev} events to {args.trace_out} "
              f"(n_dropped={tracer.n_dropped}) — load in ui.perfetto.dev")
        if metrics is not None and metrics.names():
            snap = metrics.snapshot()
            print("[trace] metrics: " + ", ".join(
                f"{k}={v:.4g}" if isinstance(v, float) else f"{k}={v}"
                for k, v in snap.items() if not isinstance(v, dict)))
    return run


if __name__ == "__main__":
    main()
