"""StageGraph executor: jitted layer-range closures with per-stage and
per-transfer wall-clock accounting.

The engine runs a compiled :class:`~repro.exec.stage_graph.StageGraph` tick
by tick in topological order:

* each :class:`StageTask` executes as ONE jitted ``apply_layers`` closure —
  requests sharing the stage are stacked into a batch, so a hotspot plan
  compiles a handful of closures no matter how many requests ride them.
  Closures are cached per ``(layer_start, layer_end)`` range; model layers
  that route through :mod:`repro.kernels` pick up Pallas kernels on TPU and
  the jnp reference paths elsewhere.  With a ``mesh``, divisible batches are
  sharded across its devices (the CPU-device-count mesh CI forces via
  ``--xla_force_host_platform_device_count``);
* each boundary :class:`Transfer` is routed through the engine's
  :class:`~repro.transport.Transport` backend.  The default
  ``InProcTransport`` reproduces the pre-transport path bit-for-bit: the
  analytic link delay (``Problem.transfer_cost()`` — the exact coefficient
  OULD minimized) plus the *measured* host serialization wall.  The
  ``loopback`` / ``multiproc`` backends move the real activation bytes
  through worker OS processes and hand the consuming stage the
  reconstructed tensor, so the measured hop wall is a realized link sample
  (per-link bandwidth accumulates on the transport for comm calibration).

``executed latency`` of a request = measured stage walls along its path +
modeled link delays — the realized counterpart of
``Evaluation.per_request_s`` (LLHR-style: judge placements on realized, not
modeled, stage times).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.profiles import ModelProfile
from ..models import cnn, vit
from ..obs import ENGINE, NULL_TRACER
from ..transport import InProcTransport, Transport
from .stage_graph import StageGraph, StageTask


@dataclasses.dataclass(frozen=True)
class StageTiming:
    """Measured execution of one batched stage launch."""

    node: int
    layer_start: int
    layer_end: int
    batch: int            # requests stacked into the launch
    wall_s: float         # measured kernel wall (post-compile, blocked)


@dataclasses.dataclass(frozen=True)
class TransferRecord:
    """One executed boundary shipment: modeled link delay + measured host
    serialization wall (device sync + copy of the activation buffer)."""

    request: int
    src_node: int
    dst_node: int
    layer: int
    nbytes: float
    delay_s: float        # modeled: nbytes × spb[src, dst]
    serialize_s: float    # measured: the transport hop wall (host
                          #   materialization for inproc; serialize + socket
                          #   round trip + reconstruct for loopback/multiproc)


@dataclasses.dataclass(frozen=True)
class ExecutionReport:
    """What actually ran: outputs plus the measured/modeled decomposition."""

    outputs: dict[int, np.ndarray]          # request row → final activation
    stage_timings: tuple[StageTiming, ...]
    transfers: tuple[TransferRecord, ...]
    executed_s: np.ndarray                  # (R,) measured comp + modeled comm
    compute_s: np.ndarray                   # (R,) measured stage walls only
    comm_s: np.ndarray                      # (R,) modeled link delays only
    predicted_s: np.ndarray | None = None   # (R,) analytic, when supplied
    transport: str = "inproc"               # backend that carried transfers

    def stage_wall(self, layer_start: int, layer_end: int) -> float:
        """Min measured wall over launches of this layer range."""
        walls = [t.wall_s for t in self.stage_timings
                 if (t.layer_start, t.layer_end) == (layer_start, layer_end)]
        if not walls:
            raise KeyError(f"no launch executed layers "
                           f"[{layer_start}, {layer_end})")
        return min(walls)

    @property
    def abs_error_s(self) -> np.ndarray:
        """|predicted − executed| per admitted request (requires predicted)."""
        assert self.predicted_s is not None, "report carries no prediction"
        mask = np.isfinite(self.executed_s) & np.isfinite(self.predicted_s)
        with np.errstate(invalid="ignore"):     # rejected rows: inf - inf
            return np.abs(np.where(mask, self.predicted_s - self.executed_s,
                                   0.0))


def layer_fns_for(profile: ModelProfile, params=None,
                  key=None) -> list[Callable]:
    """Per-unit apply functions matching ``profile``'s placement units.

    Supports the paper's CNN workloads (``lenet`` / ``vgg16``) and ViT-B/16
    (``vitb16``); other profiles must hand the engine their own
    ``layer_fns``.  ``params`` wins over ``key`` (fresh init).
    """
    key = key if key is not None else jax.random.PRNGKey(0)
    if profile.name == "lenet":
        params = params if params is not None else cnn.lenet_init(key)
        fns = cnn.lenet_layers(params)
    elif profile.name == "vgg16":
        params = params if params is not None else cnn.vgg16_init(key)
        fns = cnn.vgg16_layers(params)
    elif profile.name == "vitb16":
        params = params if params is not None else vit.vitb16_init(key)
        fns = vit.vitb16_layers(params)
    else:
        raise ValueError(
            f"no builtin layer fns for profile {profile.name!r}; "
            "pass layer_fns to ExecutionEngine directly")
    assert len(fns) == profile.num_layers
    return fns


# Arg labels of the engine's spans (DESIGN.md §9).
_SPAN_ARGS = {
    "run": ("requests", "tasks"),
    "upload": ("bytes",),
    "gather": ("rows",),
    "compile": ("layer_start", "layer_end"),
    "launch": ("batch", "units"),
    "dispatch": (),
    "split": ("rows", "copies"),
    "fetch": ("bytes",),
    "done": (),
    "stage_measure": ("layer_start", "layer_end"),
    "warm_start": ("n_ranges",),
}


class ExecutionEngine:
    """Executes stage graphs over one model's ``layer_fns``.

    One engine instance owns the jit cache, so repeated runs (the swarm
    simulator's per-epoch sampling, calibration re-measures) pay compilation
    once per unique layer range.
    """

    def __init__(self, layer_fns: Sequence[Callable], *, mesh=None,
                 data_axis: str = "data",
                 transport: Transport | None = None, tracer=None):
        self.layer_fns = list(layer_fns)
        self.mesh = mesh
        self.data_axis = data_axis
        self.transport = transport if transport is not None else InProcTransport()
        # Observability: live real-time spans (``Tracer.scope``) around the
        # host work of a run, each also a profiler annotation; nothing is
        # timed inside the jitted closures.  Transfer spans come from the
        # transport itself.
        self.tracer = tracer if tracer is not None else NULL_TRACER
        if self.tracer.enabled:
            for name, labels in _SPAN_ARGS.items():
                self.tracer.intern(name, *labels)
            set_tr = getattr(self.transport, "set_tracer", None)
            if set_tr is not None:
                set_tr(self.tracer)
        self._closures: dict[tuple[int, int], Callable] = {}
        self._splitters: dict[int, Callable] = {}
        self._warm: set[tuple[int, int, tuple]] = set()

    # -- jit cache -----------------------------------------------------------
    def closure(self, layer_start: int, layer_end: int) -> Callable:
        rng = (layer_start, layer_end)
        if rng not in self._closures:
            fns = self.layer_fns

            @jax.jit
            def _run(x, _s=layer_start, _e=layer_end):
                return cnn.apply_layers(fns, x, _s, _e)

            self._closures[rng] = _run
        return self._closures[rng]

    def _split(self, y: jax.Array, batch: int) -> Sequence[jax.Array]:
        """A launch's output as its ``batch`` request rows, each ``(1, ...)``:
        batch 1 passes ``y`` through (no device op), a larger batch is cut
        by one jitted dispatch, cached per batch size."""
        if batch == 1:
            return (y,)
        split = self._splitters.get(batch)
        if split is None:
            split = self._splitters[batch] = jax.jit(
                lambda y: tuple(y[b:b + 1] for b in range(batch)))
        return split(y)

    def _device_put(self, x: jax.Array) -> jax.Array:
        """Shard the batch dim over the mesh when it divides evenly."""
        if self.mesh is None:
            return x
        n = self.mesh.shape.get(self.data_axis, 1)
        if n <= 1 or x.shape[0] % n != 0:
            return x
        from jax.sharding import NamedSharding, PartitionSpec as P
        spec = P(self.data_axis, *([None] * (x.ndim - 1)))
        return jax.device_put(x, NamedSharding(self.mesh, spec))

    def measure_range(self, layer_start: int, layer_end: int, x, *,
                      repeats: int = 1) -> float:
        """Measured wall of layers [layer_start, layer_end) on ``x`` (min of
        ``repeats``, compile excluded) — the swarm simulator's executed-
        latency sample for a stage."""
        fn = self.closure(layer_start, layer_end)
        x = self._device_put(jnp.asarray(x))
        warm_key = (layer_start, layer_end, tuple(x.shape))
        if warm_key not in self._warm:
            jax.block_until_ready(fn(x))
            self._warm.add(warm_key)
        best = float("inf")
        with self.tracer.scope(ENGINE, "stage_measure", a0=layer_start,
                               a1=layer_end):
            for _ in range(max(1, repeats)):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(x))
                best = min(best, time.perf_counter() - t0)
        return best

    def warm_start(self, signature: Sequence[tuple[int, int]],
                   frame: np.ndarray) -> float:
        """Pre-compile the closures of a stage signature (the ``(start, end)``
        ranges of :func:`~repro.exec.stage_graph.stage_signature`) on one
        sample frame; returns the total wall.

        This is the churn-rejoin path: with the persistent compilation cache
        enabled (:mod:`repro.exec.compile_cache`) a node that joins
        mid-scenario replays compiles as disk-cache hits — milliseconds
        instead of fresh XLA compiles.  Boundary activations are propagated
        through the signature itself; a range whose start no prior range
        produced is fed through a ``[0, start)`` prefix closure.
        """
        t_begin = time.perf_counter()
        with self.tracer.scope(ENGINE, "warm_start", a0=len(signature)):
            acts: dict[int, jax.Array] = {0: jnp.asarray(frame[None])}
            for s, e in sorted(signature):
                if s not in acts:
                    acts[s] = self.closure(0, s)(acts[0])
                acts[e] = jax.block_until_ready(self.closure(s, e)(acts[s]))
                self._warm.add((s, e, tuple(acts[s].shape)))
        return time.perf_counter() - t_begin

    def _launch(self, task: StageTask, x: jax.Array) -> tuple[jax.Array, float]:
        """Run one batched stage; returns (output, measured wall seconds).
        The wall is the ``launch`` span's: from the call of the closure to
        the output ready on the device; its ``dispatch`` child ends when the
        call returns (the enqueue), the rest is the host blocked."""
        tr = self.tracer
        fn = self.closure(task.layer_start, task.layer_end)
        x = self._device_put(x)
        warm_key = (task.layer_start, task.layer_end, tuple(x.shape))
        if warm_key not in self._warm:        # compile outside the clock
            with tr.scope(ENGINE, "compile", lane=task.node,
                          a0=task.layer_start, a1=task.layer_end):
                jax.block_until_ready(fn(x))
            self._warm.add(warm_key)
        with tr.scope(ENGINE, "launch", lane=task.node,
                      a0=len(task.requests),
                      a1=task.layer_end - task.layer_start) as span:
            t0 = time.perf_counter()
            with tr.scope(ENGINE, "dispatch", lane=task.node):
                y = fn(x)
            y = jax.block_until_ready(y)
            t1 = time.perf_counter()
            span.interval(t0, t1)
        return y, t1 - t0

    # -- execution -----------------------------------------------------------
    def run(self, graph: StageGraph, frames: np.ndarray, *,
            predicted_s: np.ndarray | None = None) -> ExecutionReport:
        """Execute ``graph`` on ``frames`` (one leading row per plan request;
        rejected rows are never read).  Returns the full measured report."""
        tr = self.tracer
        with tr.scope(ENGINE, "run", a0=len(graph.requests),
                      a1=len(graph.tasks)):
            with tr.scope(ENGINE, "upload", a0=sum(
                    frames[r].nbytes for r in graph.requests)):
                acts: dict[int, jax.Array] = {
                    r: jnp.asarray(frames[r][None]) for r in graph.requests}
            timings: list[StageTiming] = []
            compute_s = np.zeros(graph.n_requests)

            transfer_by_consumer = {(link.request, link.layer): link
                                    for link in graph.transfers}
            records: list[TransferRecord] = []
            # task index of each request's last launch, for its `done`
            last = ({r: i for i, t in enumerate(graph.tasks)
                     for r in t.requests} if tr.enabled else {})

            for i, task in enumerate(graph.tasks):
                # Boundary shipments INTO this stage ride the transport
                # backend: inproc measures the host serialization of the
                # inbound activation; loopback/multiproc move its bytes to
                # the worker process owning the destination node and the
                # consuming stage reads what came back.
                for r in task.requests:
                    link = transfer_by_consumer.get((r, task.layer_start))
                    if link is None:
                        continue
                    res = self.transport.ship(link.src_node, link.dst_node,
                                              acts[r])
                    acts[r] = res.array
                    records.append(TransferRecord(
                        link.request, link.src_node, link.dst_node,
                        link.layer, link.nbytes, link.delay_s, res.wall_s))
                if len(task.requests) == 1:
                    x = acts[task.requests[0]]
                else:
                    with tr.scope(ENGINE, "gather", a0=len(task.requests)):
                        x = jnp.concatenate([acts[r] for r in task.requests])
                y, wall = self._launch(task, x)
                timings.append(StageTiming(task.node, task.layer_start,
                                           task.layer_end, len(task.requests),
                                           wall))
                if tr.enabled:
                    now = tr.now()
                    for r in task.requests:
                        if last[r] == i:
                            tr.instant(ENGINE, "done", now, frame=r)
                batch = len(task.requests)
                with tr.scope(ENGINE, "split", a0=batch, a1=int(batch > 1)):
                    for r, row in zip(task.requests, self._split(y, batch)):
                        acts[r] = row
                        compute_s[r] += wall

            comm_s = np.zeros(graph.n_requests)
            for link in graph.transfers:
                comm_s[link.request] += link.delay_s
            executed = np.full(graph.n_requests, np.inf)
            for r in graph.requests:
                executed[r] = compute_s[r] + comm_s[r]
            with tr.scope(ENGINE, "fetch") as span:
                rows = jax.device_get([acts[r] for r in graph.requests])
                outputs = {r: row[0] for r, row in zip(graph.requests, rows)}
                span.set(a0=sum(o.nbytes for o in outputs.values()))
        return ExecutionReport(outputs, tuple(timings), tuple(records),
                               executed, compute_s, comm_s, predicted_s,
                               transport=self.transport.name)

    def sequential_reference(self, frames: np.ndarray,
                             requests: Sequence[int]) -> dict[int, np.ndarray]:
        """Ground truth: every admitted request through all layers, one node."""
        fn = self.closure(0, len(self.layer_fns))
        return {r: np.asarray(fn(jnp.asarray(frames[r][None]))[0])
                for r in requests}
