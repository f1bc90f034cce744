"""Run one benchmark cell once on the accelerator this process finds.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell is looked up by name in the checkout's ``BENCHMARK.json``; its
configuration (``configs/<config>.json``), its traffic mix
(``traffic/<traffic>.json``), the driver that mix names
(``drivers/<driver>.py``) and one reader per metric
(``metrics/<metric>.py``) are files of this directory found by name, so a
cell, a mix or a metric is added by adding files and entries.

With ``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.  Diagnostics go to standard error, the numbers compared for
``correct`` last, each beside its limit; the last line of standard output
is one JSON object.  With no TPU, or fewer chips than the cell asks for,
the run exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

from harness import runtime  # noqa: E402


def metric_specs(bench: dict, workload: str, kind: str) -> list[dict]:
    return [m for m in bench[kind]
            if workload in m.get("workloads", [workload])]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    runtime.use_checkout_cache(args.workload)
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace))
    print(json.dumps(result), flush=True)
    return 0


def run_cell(workload: str, seed: int, seconds: float, tracing: bool
             ) -> dict:
    """One run of one cell; returns the result object."""
    sys.path.insert(0, str(runtime.ROOT / "src"))
    bench = runtime.load_json(runtime.ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[workload]
    chip = runtime.CHIP_DIR
    config = runtime.load_json(chip / "configs" / f"{cell['config']}.json")
    traffic = runtime.load_json(chip / "traffic" / f"{cell['traffic']}.json")

    import jax

    from harness import peaks
    from harness.context import Context

    runtime.configure_cache(workload)
    devices = runtime.accelerator(cell["chips"])
    pk = peaks.peaks_for(devices[0].device_kind)
    kind = devices[0].device_kind
    runtime.log(f"[device] platform={devices[0].platform} device_kind={kind} "
                f"count={len(devices)} jax={jax.__version__} "
                f"cache={runtime.cache_dir(workload)}")
    ctx = Context(seed, seconds, tracing, config, traffic, devices, pk,
                  T_START, runtime.CompileLog())
    driver = runtime.load_module(chip / "drivers" / f"{traffic['driver']}.py")
    rec, readings = driver.run(ctx)

    metrics = {}
    kind_key = "per_layer" if tracing else "end_to_end"
    for spec in metric_specs(bench, workload, kind_key):
        reader = runtime.load_module(chip / "metrics" / f"{spec['name']}.py")
        value = reader.read(rec)
        if value is not None:
            metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
    checks = {name: {"value": readings[name], "limit": limit}
              for name, limit in traffic["limits"].items()}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": ctx.memory_peak_bytes}
    result = {"correct": all(c["value"] <= c["limit"]
                             for c in checks.values()),
              "attempted": rec["attempted"], "failed": rec["failed"],
              "metrics": metrics, "device": device}
    if tracing:
        from harness import trace
        device["busy_s"] = rec["trace"]["busy_s"]
        device["window_s"] = rec["trace"]["window_s"]
        result["breakdown"] = trace.breakdown(rec["trace"])
    result["checks"] = checks
    runtime.log(f"[done] total_s={time.perf_counter() - T_START:.3f} "
                f"after the window: {ctx.compiles.line('after')}")
    for name, c in checks.items():
        runtime.log(f"[check] {name}={c['value']!r} limit={c['limit']!r}")
    return result


if __name__ == "__main__":
    sys.exit(main())
