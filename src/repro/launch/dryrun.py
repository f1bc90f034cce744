"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

MUST be imported/executed before any other jax usage: the first two lines
force 512 host platform devices so the production meshes can be built.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch yi_6b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh single|multi|both]

Per cell it records: memory_analysis, cost_analysis (FLOPs/bytes),
per-collective traffic parsed from the post-SPMD HLO, lower/compile wall
times — into benchmarks/artifacts/dryrun/<arch>__<shape>__<mesh>.json,
which §Roofline and the perf loop read.
"""

import os

os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_EXTRA_XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

import argparse          # noqa: E402
import dataclasses       # noqa: E402
import json              # noqa: E402
import pathlib           # noqa: E402
import re                # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from .. import configs as C                            # noqa: E402
from ..configs.base import SHAPES, ModelConfig, ShapeConfig  # noqa: E402
from ..data.pipeline import DataConfig, batch_specs    # noqa: E402
from ..models import transformer                       # noqa: E402
from ..parallel import sharding as sh                  # noqa: E402
from ..runtime import steps                            # noqa: E402
from .mesh import chips, make_production_mesh, mesh_axes  # noqa: E402

ART_DIR = pathlib.Path(__file__).resolve().parents[3] / "benchmarks" / "artifacts" / "dryrun"

# long_500k applicability: sub-quadratic archs only (DESIGN.md §5)
def cell_supported(cfg: ModelConfig, shape: ShapeConfig) -> tuple[bool, str]:
    if shape.name == "long_500k" and not cfg.subquadratic:
        return False, "SKIP(long_500k): pure full-attention arch (O(L^2) KV)"
    return True, ""


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStructs — never allocated)
# ---------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    """Abstract inputs for the step function of this shape kind."""
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=shape.seq_len,
                      global_batch=shape.global_batch,
                      embed_stub_dim=cfg.d_model if cfg.embed_stub else None)
    params = transformer.param_shapes(cfg)
    if shape.kind == "train":
        tcfg = steps.TrainConfig()
        opt = jax.eval_shape(lambda p: steps.init_opt_state(p, tcfg), params)
        return {"params": params, "opt_state": opt,
                "batch": batch_specs(dcfg, jnp.bfloat16)}
    if shape.kind == "prefill":
        return {"params": params, "batch": batch_specs(dcfg, jnp.bfloat16)}
    # decode: one new token against a cache of seq_len
    cache = transformer.cache_shapes(cfg, shape.global_batch, shape.seq_len)
    return {"params": params,
            "tokens": jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32),
            "cache": cache,
            "pos": jax.ShapeDtypeStruct((), jnp.int32)}


# ---------------------------------------------------------------------------
# sharding assembly
# ---------------------------------------------------------------------------

def _named(mesh, tree_specs):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_specs,
                        is_leaf=lambda x: isinstance(x, P))


def cache_pspecs(cache, mesh, axes: sh.MeshAxes):
    dsize = 1
    for a in axes.dp:
        dsize *= mesh.shape[a]
    msize = mesh.shape[axes.model]
    dp = axes.dp if len(axes.dp) > 1 else axes.dp[0]

    def spec(s):
        dims = s.shape
        out = [None] * len(dims)
        if len(dims) > 1 and dims[1] % dsize == 0:
            out[1] = dp
        for i in range(2, len(dims)):
            if dims[i] % msize == 0:
                out[i] = axes.model
                break
        return P(*out)

    return jax.tree.map(spec, cache)


def shardings_for(cfg: ModelConfig, shape: ShapeConfig, mesh, specs: dict):
    axes = mesh_axes(mesh)
    pspec = sh.param_pspecs(specs["params"], mesh, axes)
    pshard = _named(mesh, pspec)
    dsize = 1
    for a in axes.dp:
        dsize *= mesh.shape[a]
    bdiv = shape.global_batch % dsize == 0
    dp = axes.dp if len(axes.dp) > 1 else axes.dp[0]

    def bspec(s):
        out = [None] * len(s.shape)
        if bdiv:
            out[0] = dp
        if s.ndim == 3 and s.shape[-1] == cfg.d_model:  # embed-stub inputs
            pass
        return NamedSharding(mesh, P(*out))

    if shape.kind == "train":
        oshard = {
            "m": pshard, "v": pshard,
            "step": NamedSharding(mesh, P()),
        }
        if "comp_error" in specs["opt_state"]:
            oshard["comp_error"] = pshard
        bshard = jax.tree.map(bspec, specs["batch"])
        return {"params": pshard, "opt_state": oshard, "batch": bshard}
    if shape.kind == "prefill":
        return {"params": pshard,
                "batch": jax.tree.map(bspec, specs["batch"])}
    cshard = _named(mesh, cache_pspecs(specs["cache"], mesh, axes))
    return {"params": pshard,
            "tokens": NamedSharding(mesh, P(dp if bdiv else None, None)),
            "cache": cshard,
            "pos": NamedSharding(mesh, P())}


# ---------------------------------------------------------------------------
# HLO collective accounting
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8": 1,
                "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
                "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16}

_COLL_RE = re.compile(
    r"=\s+(\w+)\[([\d,]*)\]\S*\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")

# per-chip link-traffic weight per result byte (ring algorithms, n≫1)
_TRAFFIC_W = {"all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
              "all-to-all": 1.0, "collective-permute": 1.0}


def collective_bytes(hlo_text: str) -> dict:
    """Parse post-SPMD HLO; returns per-collective result bytes and the
    weighted per-chip link traffic (documented in DESIGN.md §8)."""
    per_op: dict[str, float] = {}
    traffic = 0.0
    for m in _COLL_RE.finditer(hlo_text):
        dtype, dims, op = m.group(1), m.group(2), m.group(3)
        size = 1
        for d in dims.split(","):
            if d:
                size *= int(d)
        b = size * _DTYPE_BYTES.get(dtype, 4)
        per_op[op] = per_op.get(op, 0.0) + b
        traffic += _TRAFFIC_W[op] * b
    per_op["weighted_link_traffic"] = traffic
    per_op["count"] = len(_COLL_RE.findall(hlo_text))
    return per_op


# ---------------------------------------------------------------------------
# cost probes: XLA counts a while-loop body ONCE, so the full-model compile
# under-reports scan flops.  We lower 1-group and 2-group variants with all
# scans unrolled and solve  cost(G) = E + G·B  exactly (E = embed/head/opt,
# B = per-group cost).  The full compile still proves shardability + memory.
# ---------------------------------------------------------------------------

def _probe_cost(cfg: ModelConfig, shape: ShapeConfig, mesh,
                train_cfg=None) -> dict:
    from ..kernels import chunked
    from ..models import transformer as tr
    period = len(cfg.block_pattern)
    out: dict = {}
    tr.UNROLL_SCANS = True
    chunked.UNROLL_SCANS = True
    try:
        costs = []
        for groups in (1, 2):
            pcfg = dataclasses.replace(cfg, n_layers=groups * period)
            specs = input_specs(pcfg, shape)
            shards = shardings_for(pcfg, shape, mesh, specs)
            if shape.kind == "train":
                fn = steps.make_train_step(
                    pcfg, train_cfg if train_cfg is not None else steps.TrainConfig())
                jitted = jax.jit(fn, in_shardings=(shards["params"],
                                                   shards["opt_state"],
                                                   shards["batch"]),
                                 donate_argnums=(0, 1))
                a = (specs["params"], specs["opt_state"], specs["batch"])
            elif shape.kind == "prefill":
                fn = steps.make_prefill_step(pcfg)
                jitted = jax.jit(fn, in_shardings=(shards["params"],
                                                   shards["batch"]))
                a = (specs["params"], specs["batch"])
            else:
                fn = steps.make_decode_step(pcfg)
                jitted = jax.jit(fn, in_shardings=(shards["params"],
                                                   shards["tokens"],
                                                   shards["cache"],
                                                   shards["pos"]),
                                 donate_argnums=(2,))
                a = (specs["params"], specs["tokens"], specs["cache"],
                     specs["pos"])
            compiled = jitted.lower(*a).compile()
            c = compiled.cost_analysis()
            c = c[0] if isinstance(c, (list, tuple)) else c
            coll = collective_bytes(compiled.as_text())
            costs.append({
                "flops": float(c.get("flops", 0.0)),
                "bytes": float(c.get("bytes accessed", 0.0)),
                "coll": coll["weighted_link_traffic"],
            })
        f1, f2 = costs
        G = cfg.n_layers // period
        for key in ("flops", "bytes", "coll"):
            B = f2[key] - f1[key]
            E = 2 * f1[key] - f2[key]
            out[f"derived_{key}_per_partition"] = E + G * B
            out[f"probe_{key}_fixed"] = E
            out[f"probe_{key}_per_group"] = B
    finally:
        tr.UNROLL_SCANS = False
        chunked.UNROLL_SCANS = False
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             save: bool = True, verbose: bool = True,
             cfg_transform=None, tag: str = "",
             train_cfg: "steps.TrainConfig | None" = None) -> dict:
    """``cfg_transform``: optional ModelConfig→ModelConfig hook — the perf
    loop's knob (chunk sizes, capacity factors, …).  ``tag`` suffixes the
    artifact name so optimized variants never overwrite the paper-faithful
    baseline artifacts."""
    cfg = C.get_config(arch).production()
    if cfg_transform is not None:
        cfg = cfg_transform(cfg)
    shape = SHAPES[shape_name]
    mesh_name = ("multi" if multi_pod else "single") + (f"__{tag}" if tag else "")
    ok, why = cell_supported(cfg, shape)
    rec: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_name}
    if not ok:
        rec.update({"status": "skipped", "reason": why})
        if save:
            _save(rec)
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    axes = mesh_axes(mesh)
    sh.set_active_mesh(mesh, axes)
    try:
        specs = input_specs(cfg, shape)
        shards = shardings_for(cfg, shape, mesh, specs)

        tcfg = train_cfg if train_cfg is not None else steps.TrainConfig()
        if shape.kind == "train":
            fn = steps.make_train_step(cfg, tcfg)
            jitted = jax.jit(
                fn,
                in_shardings=(shards["params"], shards["opt_state"],
                              shards["batch"]),
                out_shardings=(shards["params"], shards["opt_state"], None),
                donate_argnums=(0, 1))
            args = (specs["params"], specs["opt_state"], specs["batch"])
        elif shape.kind == "prefill":
            fn = steps.make_prefill_step(cfg)
            jitted = jax.jit(fn, in_shardings=(shards["params"],
                                               shards["batch"]))
            args = (specs["params"], specs["batch"])
        else:
            fn = steps.make_decode_step(cfg)
            jitted = jax.jit(
                fn,
                in_shardings=(shards["params"], shards["tokens"],
                              shards["cache"], shards["pos"]),
                out_shardings=(None, shards["cache"]),
                donate_argnums=(2,))
            args = (specs["params"], specs["tokens"], specs["cache"],
                    specs["pos"])

        t0 = time.perf_counter()
        lowered = jitted.lower(*args)
        t_lower = time.perf_counter() - t0
        t0 = time.perf_counter()
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0

        mem = compiled.memory_analysis()
        mem_rec = {k: getattr(mem, k) for k in dir(mem)
                   if k.endswith("bytes") or k.endswith("_in_bytes")
                   and not k.startswith("_")}
        if "peak_memory_in_bytes" not in mem_rec:
            # older jaxlib memory_analysis lacks the peak field; derive it
            # (aliased/donated argument bytes are not held twice)
            mem_rec["peak_memory_in_bytes"] = (
                mem_rec.get("argument_size_in_bytes", 0)
                + mem_rec.get("output_size_in_bytes", 0)
                + mem_rec.get("temp_size_in_bytes", 0)
                - mem_rec.get("alias_size_in_bytes", 0))
        cost = compiled.cost_analysis()
        cost = cost[0] if isinstance(cost, (list, tuple)) else cost
        coll = collective_bytes(compiled.as_text())

        probe = _probe_cost(cfg, shape, mesh, train_cfg=train_cfg)
        rec.update({
            "status": "ok",
            "chips": chips(mesh),
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "memory": mem_rec,
            "flops_per_partition": float(cost.get("flops", -1.0)),
            "bytes_per_partition": float(cost.get("bytes accessed", -1.0)),
            "collectives": coll,
            **probe,
        })
        if verbose:
            print(f"[dryrun] {arch} {shape_name} {mesh_name}: "
                  f"compile={t_compile:.1f}s flops/part={rec['flops_per_partition']:.3e} "
                  f"coll={coll['weighted_link_traffic']:.3e}B")
            print(f"[dryrun]   memory_analysis: {mem_rec}")
    except Exception as e:  # noqa: BLE001 — record failures as data
        rec.update({"status": "error", "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-2000:]})
        if verbose:
            print(f"[dryrun] {arch} {shape_name} {mesh_name}: FAILED {e}")
    finally:
        sh.set_active_mesh(None)
    if save:
        _save(rec)
    return rec


def _save(rec: dict) -> None:
    ART_DIR.mkdir(parents=True, exist_ok=True)
    p = ART_DIR / f"{rec['arch']}__{rec['shape']}__{rec['mesh']}.json"
    p.write_text(json.dumps(rec, indent=1, default=str))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    archs = list(C.ARCH_IDS) if args.arch is None else [args.arch]
    shapes = list(SHAPES) if args.shape is None else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    if not (args.all or (args.arch and args.shape)):
        ap.error("pass --all or both --arch and --shape")

    n_ok = n_skip = n_err = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                mesh_name = ("multi" if mp else "single") + \
                    (f"__{args.tag}" if args.tag else "")
                out = ART_DIR / f"{arch}__{shape}__{mesh_name}.json"
                if args.skip_existing and out.exists():
                    prev = json.loads(out.read_text())
                    if prev.get("status") in ("ok", "skipped"):
                        continue
                rec = run_cell(arch, shape, mp, tag=args.tag)
                n_ok += rec["status"] == "ok"
                n_skip += rec["status"] == "skipped"
                n_err += rec["status"] == "error"
    print(f"[dryrun] done: {n_ok} ok, {n_skip} skipped, {n_err} errors")


if __name__ == "__main__":
    main()
