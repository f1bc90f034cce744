"""Process-level plumbing of one benchmark run: the compile cache inside the
checkout, the device check, compile events, host spans and the result line.
"""

from __future__ import annotations

import collections
import contextlib
import importlib.util
import json
import os
import sys
import time
from pathlib import Path

CHIP_DIR = Path(__file__).resolve().parents[1]        # benchmarks/chip
ROOT = CHIP_DIR.parents[1]                            # the checkout
CACHE_ROOT = ROOT / ".jax_cache" / "chipbench"
# Executables larger than this are not kept: the program's stage closures
# embed their weights, which the seed draws anew each run, so they never
# hit again; the bound keeps a run from writing them (~0.5 GB each) and
# evicts the least recently used entries past it.
CACHE_MAX_BYTES = 256 << 20


def cache_dir(workload: str) -> Path:
    return CACHE_ROOT / workload


def use_checkout_cache(workload: str) -> None:
    """Point JAX's persistent compilation cache at one fixed directory of
    the checkout per cell, before JAX is imported, whatever the environment
    says: the program reads ``JAX_COMPILATION_CACHE_DIR`` and sets no
    other.  One directory per cell, so one cell's entries never evict
    another's."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir(workload))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def configure_cache(workload: str) -> None:
    import jax
    path = cache_dir(workload)
    path.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_max_size", CACHE_MAX_BYTES)


def accelerator(chips: int):
    """The devices of the cell, or SystemExit when JAX finds no TPU or too
    few of them."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"needs a TPU; JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"the cell asks for {chips} chips; JAX found "
                         f"{len(devs)}")
    return devs[:chips]


def load_module(path: Path, name: str | None = None):
    """Import a file of the benchmark by its path (names may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        name or "bench_" + path.stem.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


class CompileLog:
    """Backend compile requests (cache hits included) and their seconds,
    from JAX's own monitoring events, split by phase."""

    def __init__(self):
        import jax
        self.phase = "setup"
        self.n = collections.Counter()
        self.secs = collections.Counter()
        self.hits = collections.Counter()
        self.misses = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, name: str, secs: float, **_) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.n[self.phase] += 1
            self.secs[self.phase] += secs

    def _on_event(self, name: str, **_) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.hits[self.phase] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses[self.phase] += 1

    def line(self, phase: str) -> str:
        return (f"compiles={self.n[phase]} seconds={self.secs[phase]:.3f} "
                f"cache_hits={self.hits[phase]} "
                f"cache_misses={self.misses[phase]}")


class Spans:
    """Host spans taken by the benchmark around its calls into the program.
    With ``annotate`` each span is also a ``TraceAnnotation`` in the
    profiler's trace, on the device's clock, so idle gaps can be named."""

    def __init__(self, annotate: bool):
        self.annotate = annotate
        self.secs: dict[str, list[float]] = collections.defaultdict(list)

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax
        ann = (jax.profiler.TraceAnnotation("bench." + name) if self.annotate
               else contextlib.nullcontext())
        t0 = time.perf_counter()
        with ann:
            yield
        self.secs[name].append(time.perf_counter() - t0)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def reference(name: str):
    """The plain reference module ``references/<name>.py``."""
    return load_module(CHIP_DIR / "references" / f"{name}.py",
                       f"bench_reference_{name}")
