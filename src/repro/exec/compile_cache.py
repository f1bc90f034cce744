"""Persistent XLA compilation cache for the jitted ``apply_layers`` closures.

Engine warmup used to be per-process and thrown away: every simulation,
benchmark, and churn-rejoined node paid a fresh XLA compile per layer range.
This module points JAX's compilation cache at a durable directory so a
recompile of an already-seen closure is a disk hit — measured ~10× faster
on the CPU backend, which is what lets a node that joins mid-scenario warm
in milliseconds (``ExecutionEngine.warm_start``).

Lifecycle:

* :func:`enable` — set the cache directory (the standard
  ``JAX_COMPILATION_CACHE_DIR`` env var > argument > :data:`DEFAULT_DIR`,
  one fixed directory inside the checkout: the path is part of the cache's
  key, so a directory that moves never hits) and drop the min-compile-time /
  min-entry-size thresholds so CPU kernels are cached at all (the defaults
  assume multi-second accelerator compiles);
* :func:`disable` — detach the directory (in-memory jit cache untouched);
* :func:`clear_in_memory` — drop the in-memory executable cache, which is
  exactly what a process restart does: the next compile of the same HLO
  must go through the persistent layer, making warm-vs-cold measurable
  in-process (:func:`measure_warm_start`, bench E6's strict lock).

CI keeps the directory across runs with ``actions/cache`` keyed on the JAX
version, so the suite's compiles warm across workflow runs too.
"""

from __future__ import annotations

import dataclasses
import os
import time
from pathlib import Path
from typing import Callable, Sequence

import jax
import jax.numpy as jnp

from ..models import cnn

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def _reset_backend_cache() -> None:
    """JAX initializes its persistent-cache singleton on first compile and
    never re-reads the config afterwards; without this reset, enabling (or
    re-pointing) the cache in a process that already compiled something is
    a silent no-op."""
    from jax.experimental.compilation_cache import compilation_cache
    compilation_cache.reset_cache()


def enable(cache_dir: str | os.PathLike | None = None) -> Path:
    """Attach the persistent compilation cache; returns the directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache and no other
    directory is set here; otherwise ``cache_dir``, else :data:`DEFAULT_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    return _attach(Path(env or cache_dir or DEFAULT_DIR))


def _attach(path: Path) -> Path:
    path.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(path))
    # CPU closures compile in ~0.1–1 s and produce small executables; the
    # default thresholds would silently cache nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _reset_backend_cache()
    return path


def disable() -> None:
    jax.config.update("jax_compilation_cache_dir", None)
    _reset_backend_cache()


def is_enabled() -> bool:
    return jax.config.jax_compilation_cache_dir is not None


def cache_dir() -> Path | None:
    d = jax.config.jax_compilation_cache_dir
    return Path(d) if d else None


def clear_in_memory() -> None:
    """Drop compiled executables from process memory (what a restart does);
    the persistent directory is untouched, so the next compile is a disk
    hit when the cache is enabled."""
    jax.clear_caches()


@dataclasses.dataclass(frozen=True)
class WarmStartReport:
    """Cold-vs-warm compile walls over one set of layer ranges."""

    ranges: tuple[tuple[int, int], ...]
    cold_s: tuple[float, ...]     # fresh compile, empty persistent cache
    warm_s: tuple[float, ...]     # recompile after clear_in_memory(): disk hit

    @property
    def cold_total_s(self) -> float:
        return float(sum(self.cold_s))

    @property
    def warm_total_s(self) -> float:
        return float(sum(self.warm_s))

    @property
    def speedup(self) -> float:
        return (self.cold_total_s / self.warm_total_s
                if self.warm_total_s > 0 else float("inf"))

    def summary(self) -> str:
        return (f"warm start: {len(self.ranges)} ranges, "
                f"cold {self.cold_total_s:.3f}s -> warm "
                f"{self.warm_total_s * 1e3:.1f}ms "
                f"({self.speedup:.1f}x)")


def measure_warm_start(layer_fns: Sequence[Callable],
                       ranges: Sequence[tuple[int, int]],
                       frame, *, cache_dir: str | os.PathLike
                       ) -> WarmStartReport:
    """Measure the persistent cache's churn-rejoin benefit on ``ranges``.

    Pass one: compile each range's closure against ``cache_dir`` (cold —
    the caller hands a fresh directory for a deterministic baseline, which
    is why benches do NOT reuse the CI-level cache here).  Then
    :func:`clear_in_memory` simulates the process restart of a rejoining
    node and pass two recompiles the same ranges — every compile now lands
    on the disk cache.  ``ranges`` must chain from layer 0 (each start
    produced by an earlier range) so boundary activations can propagate.

    The previously configured cache directory is restored on exit.  This
    measures the cache mechanism on the CPU: it attaches ``cache_dir`` itself,
    even where ``JAX_COMPILATION_CACHE_DIR`` is set.
    """
    ranges = tuple((int(s), int(e)) for s, e in ranges)
    if not ranges or ranges[0][0] != 0:
        raise ValueError(f"ranges must chain from layer 0, got {ranges}")
    prev = jax.config.jax_compilation_cache_dir
    _attach(Path(cache_dir))
    fns = list(layer_fns)

    def build(s: int, e: int) -> Callable:
        @jax.jit
        def _run(x, _s=s, _e=e):
            return cnn.apply_layers(fns, x, _s, _e)
        return _run

    def timed_pass() -> tuple[list[float], dict]:
        acts = {0: jnp.asarray(frame)[None]}
        walls = []
        for s, e in ranges:
            if s not in acts:
                raise ValueError(f"range ({s}, {e}) has no produced start")
            fn = build(s, e)
            t0 = time.perf_counter()
            y = jax.block_until_ready(fn(acts[s]))
            walls.append(time.perf_counter() - t0)
            acts[e] = y
        return walls, acts

    try:
        cold, _ = timed_pass()
        clear_in_memory()                  # the "process restart"
        warm, _ = timed_pass()
    finally:
        if prev:
            jax.config.update("jax_compilation_cache_dir", prev)
            _reset_backend_cache()
        else:
            disable()
    return WarmStartReport(ranges, tuple(cold), tuple(warm))
