"""Multi-process transport: one JAX process per simulated node group.

SNIPPETS.md §2's multi-controller model — each process owns its local
devices and must be launched explicitly — scaled down to one machine: every
worker is a full JAX process (``repro.transport.worker --jax``) and each
shipped activation is put on the worker's default device before being
echoed, so the bytes cross a process boundary *and* a host→device buffer
copy on the receiving side (device-to-device movement where the platform
provides it; on the CPU backend this is the host↔device-buffer copy pair).

Node → process ownership follows the swarm's mobility groups when a
``group_of`` array is supplied (one JAX process per group, the SNIPPETS §2
"one process per host" unit), else round-robin over ``n_workers``.
"""

from __future__ import annotations

import jax
import numpy as np

from .loopback import LoopbackTransport


class MultiProcTransport(LoopbackTransport):
    name = "multiproc"
    _jax_workers = True

    def __init__(self, *, n_workers: int | None = None,
                 group_of: np.ndarray | None = None,
                 timeout_s: float = 300.0):
        node_of = None
        if group_of is not None:
            group_of = np.asarray(group_of, np.int64)
            n_groups = int(group_of.max()) + 1 if group_of.size else 1
            n_workers = n_workers if n_workers is not None else n_groups
            node_of = {int(i): int(g) for i, g in enumerate(group_of)}
        super().__init__(n_workers=n_workers or 2, node_of=node_of,
                         timeout_s=timeout_s)

    def start(self) -> None:
        # A chip belongs to one process: this parent holds it, so a JAX
        # worker could never get it and the parent would wait out its
        # timeout.  Nodes as devices of one process is ROADMAP R8.
        if not self.started and jax.default_backend() == "tpu":
            raise RuntimeError(
                "multiproc transport spawns JAX worker processes, but this "
                "process holds the TPU and a chip serves one process; use "
                "the inproc or loopback transport on a chip host")
        super().start()
