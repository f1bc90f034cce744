"""The lower-precision control of a cell, on the accelerator it finds:

    python3 benchmarks/chip/control.py --workload <name> --seeds 1 2 3

For each seed the cell's driver puts its plain reference, recomputed in the
next precision below the configuration's, in the program's place and
compares it with the reference as a run compares the program.  Prints each
reading beside the cell's limit; a sound limit sees every control fail.
The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from harness import runtime  # noqa: E402


def readings(workload: str, seed: int) -> tuple[dict, dict]:
    """(control readings, the cell's limits) for one seed."""
    sys.path.insert(0, str(runtime.ROOT / "src"))
    bench = runtime.load_json(runtime.ROOT / "BENCHMARK.json")
    cell = {w["name"]: w for w in bench["workloads"]}[workload]
    chip = runtime.CHIP_DIR
    config = runtime.load_json(chip / "configs" / f"{cell['config']}.json")
    traffic = runtime.load_json(chip / "traffic" / f"{cell['traffic']}.json")
    driver = runtime.load_module(chip / "drivers" / f"{traffic['driver']}.py")
    return driver.control(config, traffic, seed), traffic["limits"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    runtime.use_checkout_cache(args.workload)
    import jax
    runtime.configure_cache(args.workload)
    dev = jax.devices()[0]
    runtime.log(f"[device] platform={dev.platform} "
                f"device_kind={dev.device_kind}")
    failed_all = True
    for seed in args.seeds:
        got, limits = readings(args.workload, seed)
        fails = [k for k, lim in limits.items() if got[k] > lim]
        failed_all &= bool(fails)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "readings": got, "limits": limits,
                          "control_fails_on": fails}), flush=True)
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main())
