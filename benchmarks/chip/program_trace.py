"""One traced run of a cell with the program's own tracer attached:

    python3 benchmarks/chip/program_trace.py --workload <name> --seed <n> \\
        --seconds <s> [--out <path.json>]

The run is ``run.py --trace 1``'s, except that one ``repro.obs.Tracer`` is
handed to every ``ExecutionEngine`` and ``AdmissionController`` the cell
builds, so the engine, the transport and admission time themselves
with live spans that also land in the profiler trace (``repro.<track>.<name>``
host events).  The result line is ``run.py``'s, plus:

* under ``metrics``, the per-layer numbers read from the window's program
  spans (``harness/program.py``'s ``READERS``);
* under ``breakdown``, ``program_gaps``: the device's idle time named by the
  innermost program span open in it;
* under ``program``: each span's p50 and p95 seconds per round, the bytes a
  frame moves device-to-host, the device seconds per CNN unit, the rounds of
  the window and ``n_dropped`` (events of the window the ring lost).

The benchmark's own runs never run this; it names where a round's host time
goes.  Like ``run.py`` it exits non-zero without a TPU.
"""

from __future__ import annotations

import contextlib
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from harness import program, runtime, trace  # noqa: E402
from harness.context import Context  # noqa: E402


@contextlib.contextmanager
def attached(tracer, seen: dict):
    """Hand ``tracer`` to the engines and admission controllers built inside
    the block, note the ring's sequence number when the window opens and
    closes, add the window's program spans to the record (kept in
    ``seen["rec"]``) and the program's reductions to the trace's."""
    from repro import exec as rexec
    from repro.runtime import serve

    def traced(cls):
        class Traced(cls):
            def __init__(self, *a, **kw):
                kw.setdefault("tracer", tracer)
                super().__init__(*a, **kw)
        return Traced

    def setup_done(ctx):
        real["setup_done"](ctx)
        seen["since"] = tracer.seq

    def window_closed(ctx):
        seen["until"] = tracer.seq
        real["window_closed"](ctx)

    def record(ctx, **kw):
        rec = real["record"](ctx, **kw)
        rec["program"] = program.window(tracer, seen["since"], seen["until"])
        seen["rec"] = rec
        return rec

    def reduce(space):
        return {**real["reduce"](space), **program.reduce(space)}

    real = {"engine": rexec.ExecutionEngine,
            "admission": serve.AdmissionController,
            "setup_done": Context.setup_done,
            "window_closed": Context.window_closed,
            "record": Context.record, "reduce": trace.reduce}
    rexec.ExecutionEngine = traced(real["engine"])
    serve.AdmissionController = traced(real["admission"])
    Context.setup_done, Context.window_closed = setup_done, window_closed
    Context.record, trace.reduce = record, reduce
    try:
        yield
    finally:
        rexec.ExecutionEngine = real["engine"]
        serve.AdmissionController = real["admission"]
        Context.setup_done = real["setup_done"]
        Context.window_closed = real["window_closed"]
        Context.record, trace.reduce = real["record"], real["reduce"]


def run_traced(workload: str, seed: int, seconds: float,
               tracing: bool = True) -> tuple[dict, dict]:
    """``run.run_cell`` with the program's tracer attached (see the module
    docstring); returns the result and the run's record.
    ``tracing=False`` takes no profiler trace, so the result carries no
    ``program_gaps`` and no unit seconds."""
    sys.path.insert(0, str(runtime.ROOT / "src"))
    import run
    from repro.obs import Tracer

    tracer, seen = Tracer(), {}
    with attached(tracer, seen):
        result = run.run_cell(workload, seed, seconds, tracing)
    rec = seen["rec"]
    for name, read in program.READERS.items():
        value = read(rec)
        if value is not None:
            result["metrics"][name] = {"value": value, "unit": "ms"}
    runs = rec["program"]["spans"].get("engine.run", {"ts": []})
    summary = {"rounds": len(runs["ts"]),
               "n_dropped": rec["program"]["n_dropped"],
               "span_round_s": program.per_round(rec),
               "d2h_bytes_per_frame": program.d2h_bytes_per_frame(rec)}
    if rec["trace"]:
        gaps = rec["trace"]["program_gap_s"]
        result.setdefault("breakdown", {})["program_gaps"] = [
            [k, v] for k, v in sorted(gaps.items(), key=lambda kv: -kv[1])]
        summary["scope_s"] = rec["trace"]["scope_s"]
    result["program"] = summary
    checks = result.pop("checks")
    result["checks"] = checks                   # the line's last key
    return result, rec


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None,
                    help="also write the result object to this file")
    args = ap.parse_args(argv)
    runtime.use_checkout_cache(args.workload)
    result, _ = run_traced(args.workload, args.seed, args.seconds)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(result, indent=1))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
