"""Smoke of the served path on one TPU chip, through the user entry points.

    python chip_smoke.py

Phases, all in this one process (a chip serves one process; nothing here
starts a JAX child):

(a) serve — ``repro.launch.serve.main`` at full width: ``internlm2_1p8b``
    in bf16, batch 4, a 128-token prompt and 16 decode steps, plus
    ``--execute --model vgg16`` on 326x595 frames over an 8-node pool placed
    by ``ould-dp-sparse`` with the ``inproc`` transport.  Checks that the
    prefill and decode executables hold Pallas kernels (``tpu_custom_call``),
    that the first decode logits agree with the ``ref.py`` path, that the
    CNN plan splits a request over nodes with a transfer, and that every
    admitted CNN output equals ``engine.sequential_reference``.
(b) swarm — the bench S7 epoch re-solve (N=1024, 64 hotspots) with the
    batched f64 sweep, which must be bit-identical to the sequential
    planner, then a short executed ``runtime.swarm.simulate`` of VGG16.

Earlier lines report the device, each phase's wall and compile seconds, the
peak device memory and the compile cache directory.  The last line is one
JSON object naming the device.  With no TPU the script exits non-zero and
prints no result.
"""

from __future__ import annotations

import collections
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "internlm2_1p8b"
BATCH, PROMPT_LEN, STEPS = 4, 128, 16
# Pallas vs ref.py first decode logits, both bf16 params and activations:
# max |pallas - ref| <= LOGIT_RTOL * max |ref|.  The two paths round
# attention operands to bf16 at different points (the Pallas kernels
# contract in f32 in VMEM, XLA's f32 einsum at default precision takes
# bf16 passes), and 24 layers carry the difference in a bf16 stream.
LOGIT_RTOL = 5e-2
# Engine (split over nodes) vs one-node sequential VGG16: same f32 ops at
# JAX's default matmul precision (on TPU: bf16 operand passes, f32
# accumulation) in differently fused programs, so only the summation order
# may differ: max |engine - reference| <= CNN_RTOL * max |reference|.
CNN_RTOL = 1e-2

_compile_s: collections.Counter = collections.Counter()


def _on_event(name: str, secs: float, **_) -> None:
    if name.startswith("/jax/core/compile/"):
        _compile_s["total"] += secs


class Phase:
    """Times one phase; the compile seconds come from JAX's own events."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.t0, self.c0 = time.perf_counter(), _compile_s["total"]
        return self

    def __exit__(self, *exc):
        import jax
        wall = time.perf_counter() - self.t0
        comp = _compile_s["total"] - self.c0
        peak = jax.devices()[0].memory_stats()["peak_bytes_in_use"]
        status = "failed" if exc[0] is not None else "ok"
        print(f"[phase] {self.name} {status}: wall={wall:.3f}s "
              f"compile={comp:.3f}s run={wall - comp:.3f}s "
              f"peak_bytes_in_use={peak}", flush=True)


def check(ok: bool, msg: str) -> None:
    """A check that ``python -O`` keeps."""
    if not ok:
        raise AssertionError(msg)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def _ref_logits(run, tok, pos):
    """Prefill and first decode logits of a second Server over the same
    params whose ops take the ref.py path (the ``REPRO_KERNELS=xla``
    dispatch of ``repro.kernels.ops``, read when a step is traced)."""
    from repro.kernels import ops
    from repro.runtime.serve import Server

    prev = os.environ.get("REPRO_KERNELS")
    os.environ["REPRO_KERNELS"] = "xla"
    ops._mode.cache_clear()
    try:
        srv = Server(run.server.cfg, run.server.params, run.server.scfg)
        logits, cache = srv.prefill(srv.params, {"tokens": run.prompts})
        lowered = srv.decode.lower(srv.params, tok, cache, pos)
        check("tpu_custom_call" not in lowered.as_text(),
              "the ref.py decode step still holds a Pallas kernel")
        first, _ = srv.decode(srv.params, tok, cache, pos)
        return logits, first
    finally:
        if prev is None:
            del os.environ["REPRO_KERNELS"]
        else:
            os.environ["REPRO_KERNELS"] = prev
        ops._mode.cache_clear()


def check_lm(run) -> None:
    import jax.numpy as jnp
    import numpy as np

    srv = run.server
    batch = {"tokens": jnp.asarray(run.prompts)}
    prefill = srv.prefill.lower(srv.params, batch).compile()
    logits, cache = prefill(srv.params, batch)
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    pos = jnp.int32(PROMPT_LEN)
    decode = srv.decode.lower(srv.params, tok, cache, pos).compile()
    check(_has_kernel(prefill), "prefill executable has no Pallas kernel")
    check(_has_kernel(decode), "decode executable has no Pallas kernel")
    first, _ = decode(srv.params, tok, cache, pos)
    ref_logits, ref_first = _ref_logits(run, tok, pos)
    for name, got, want in (("prefill", logits, ref_logits),
                            ("decode", first, ref_first)):
        got, want = np.asarray(got), np.asarray(want)
        check(got.shape == (BATCH, srv.cfg.vocab) and np.isfinite(got).all(),
              f"{name} logits: shape {got.shape} or non-finite values")
        err = float(np.abs(got - want).max() / np.abs(want).max())
        print(f"[check] {name} logits pallas vs ref.py: "
              f"max|d|/max|ref|={err:.3e} (tol {LOGIT_RTOL})", flush=True)
        check(err <= LOGIT_RTOL, f"{name} logits drift from ref.py: {err}")
    print("[check] prefill and decode executables hold tpu_custom_call")


def check_cnn(run) -> None:
    import numpy as np

    plan, graph, report = run.cnn_plan, run.graph, run.report
    admitted = np.flatnonzero(plan.admitted)
    split = [r for r in admitted if len(set(plan.assign[r].tolist())) >= 2]
    check(bool(split) and bool(graph.transfers),
          f"no request split over nodes: assign={plan.assign.tolist()}")
    ref = run.engine.sequential_reference(run.frames, list(admitted))
    worst = 0.0
    for r in admitted:
        out = report.outputs[r]
        check(np.isfinite(out).all() and out.shape == ref[r].shape,
              f"request {r}: shape {out.shape} or non-finite output")
        worst = max(worst, float(np.abs(out - ref[r]).max()
                                 / np.abs(ref[r]).max()))
    print(f"[check] vgg16 admitted={len(admitted)} split={len(split)} "
          f"transfers={len(graph.transfers)} engine vs sequential: "
          f"max|d|/max|ref|={worst:.3e} (tol {CNN_RTOL})", flush=True)
    check(worst <= CNN_RTOL, f"engine output drifts from reference: {worst}")


def phase_serve() -> None:
    from repro.launch import serve

    run = serve.main([
        "--arch", ARCH, "--full-width", "--batch", str(BATCH),
        "--prompt-len", str(PROMPT_LEN), "--steps", str(STEPS),
        "--planner", "ould-dp-sparse", "--pool-nodes", "8",
        "--execute", "--model", "vgg16", "--transport", "inproc"])
    check(run.generated.shape == (BATCH, STEPS),
          f"generated {run.generated.shape}")
    check_lm(run)
    check_cnn(run)


def phase_swarm() -> None:
    import numpy as np

    from benchmarks.common import HIGH_MEM, snapshot_problem
    from repro.core import SnapshotView, batch_dp, get_planner, vgg16_profile
    from repro.runtime.swarm import SwarmScenario, simulate

    # The bench S7 instance: a provisioned swarm, 64 hotspot sources.
    prob = snapshot_problem("lenet", 1024, 1024, mem=8 * HIGH_MEM,
                            area=300.0, seed=0, hotspots=64)
    view = SnapshotView(prob.rates)
    seq = get_planner("ould-dp-sparse").plan(prob, view)
    bat = get_planner("ould-dp-sparse", batch_solve=True).plan(prob, view)
    where = sorted(str(d) for d in batch_dp._spb_cache[1].devices())
    print(f"[swarm] S7 N=1024: batched sweep ran on {where}, "
          f"compiles={batch_dp.compile_count()}, "
          f"batched={bat.solve_stats.n_batched}/1024, "
          f"admitted={bat.n_admitted}", flush=True)
    check("TPU" in where[0], f"the sweep ran on {where}")
    check(bat.solve_stats.n_batched > 0, "no request took the batched sweep")
    check(np.array_equal(seq.admitted, bat.admitted), "admitted differs")
    check(np.array_equal(seq.assign, bat.assign), "assign differs")
    check(seq.objective == bat.objective,
          f"objective differs: {seq.objective!r} vs {bat.objective!r}")

    scn = SwarmScenario(execute=True, batch_solve=True, queue_model="perhop",
                        duration_ticks=45, epoch_ticks=15)
    res = simulate(scn, "incremental-sparse", profile=vgg16_profile())
    print(f"[swarm] executed vgg16 {scn.frame_hw}: epochs={len(res.epochs)} "
          f"arrivals={res.n_arrivals} served={res.served} "
          f"missed={res.missed} p50={res.p50_latency_s:.4f}s", flush=True)
    check(res.served > 0, "the executed swarm served no frame")


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    from repro.exec import compile_cache

    cache = compile_cache.enable()
    jax.monitoring.register_event_duration_secs_listener(_on_event)
    print(f"[device] platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())} jax={jax.__version__}", flush=True)
    print(f"[cache] compile cache dir: {cache}", flush=True)
    with Phase("serve"):
        phase_serve()
    with Phase("swarm"):
        phase_swarm()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
