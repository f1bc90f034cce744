"""Loopback transport: real activation bytes through real OS processes.

The parent spawns ``n_workers`` worker processes (``repro.transport.worker``)
and connects each over localhost TCP.  Shipping an activation from node
``src`` to node ``dst``:

1. materialize the device array on host and serialize it (contiguous copy),
2. send the bytes to the worker process owning ``dst`` (length-prefixed
   frame: the payload crosses two kernel socket buffers and lives briefly
   in a second address space),
3. receive the echoed bytes back and reconstruct the array the consuming
   stage reads — so downstream correctness *depends on* transport fidelity
   rather than being assumed.

The measured wall covers the full hop (serialize + round trip +
reconstruct); realized bandwidth is charged conservatively as
``payload / wall``.  Node → worker ownership defaults to round-robin and
accepts an explicit ``node_of`` map (the multi-proc backend maps by mobility
group).
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys
import time
from pathlib import Path

import jax
import numpy as np

from ..obs import TRANSPORT
from .base import ShipResult, TransportBase, WorkerStats
from .worker import (OP_HELLO, OP_QUIT, OP_REPLY, OP_SHIP, REPLY_TIMES,
                     recv_frame, send_frame)


class LoopbackTransport(TransportBase):
    name = "loopback"
    _jax_workers = False      # MultiProcTransport flips this

    def __init__(self, *, n_workers: int = 2,
                 node_of: dict[int, int] | None = None,
                 timeout_s: float = 120.0):
        super().__init__()
        if n_workers < 1:
            raise ValueError("loopback transport needs at least one worker")
        self.n_workers = int(n_workers)
        self._node_of = dict(node_of) if node_of else None
        self._timeout_s = float(timeout_s)
        self._procs: list[subprocess.Popen] = []
        self._conns: list[socket.socket] = []
        self.worker_pids: list[int] = []
        self.worker_backends: list[str | None] = []
        # Worker-side timing shipped back in every OP_REPLY header,
        # accumulated per worker index (the obs per-worker track's source).
        self.worker_stats: dict[int, WorkerStats] = {}

    # -- lifecycle -----------------------------------------------------------
    @property
    def started(self) -> bool:
        return bool(self._conns)

    def start(self) -> None:
        if self.started:
            return
        import json

        server = socket.create_server(("127.0.0.1", 0))
        server.settimeout(self._timeout_s)
        port = server.getsockname()[1]
        # worker.py runs as a plain script: stdlib-only startup (no package
        # import), so plain workers come up in milliseconds — churn rejoin
        # spawns them mid-scenario.
        cmd = [sys.executable, str(Path(__file__).with_name("worker.py")),
               "--connect", f"127.0.0.1:{port}"]
        if self._jax_workers:
            cmd.append("--jax")
        try:
            for _ in range(self.n_workers):
                self._procs.append(subprocess.Popen(cmd, env=dict(os.environ)))
            for _ in range(self.n_workers):
                conn, _ = server.accept()
                conn.settimeout(self._timeout_s)
                conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                op, payload = recv_frame(conn)
                if op != OP_HELLO:
                    raise ConnectionError(f"expected worker hello, got {op!r}")
                hello = json.loads(payload)
                self._conns.append(conn)
                self.worker_pids.append(int(hello["pid"]))
                self.worker_backends.append(hello.get("backend"))
        except Exception:
            self.close()
            raise
        finally:
            server.close()

    def close(self) -> None:
        for conn in self._conns:
            try:
                send_frame(conn, OP_QUIT)
            except OSError:
                pass
            conn.close()
        self._conns = []
        for p in self._procs:
            try:
                p.wait(timeout=10.0)
            except subprocess.TimeoutExpired:
                p.kill()
        self._procs = []

    # -- shipping ------------------------------------------------------------
    def worker_of(self, node: int) -> int:
        if self._node_of is not None and node in self._node_of:
            return self._node_of[node] % self.n_workers
        return node % self.n_workers

    def ship(self, src_node: int, dst_node: int, array) -> ShipResult:
        if not self.started:
            self.start()
        worker = self.worker_of(dst_node)
        conn = self._conns[worker]
        with self._tracer.scope(TRANSPORT, "ship", lane=src_node) as span:
            t0 = time.perf_counter()
            host = np.ascontiguousarray(
                np.asarray(jax.block_until_ready(array)))
            payload = host.tobytes()
            send_frame(conn, OP_SHIP, payload)
            op, reply = recv_frame(conn)
            if op != OP_REPLY or len(reply) != len(payload) + REPLY_TIMES.size:
                raise ConnectionError(
                    f"transport worker returned {op!r}/{len(reply)}B "
                    f"for a {len(payload)}B shipment")
            recv_s, echo_s = REPLY_TIMES.unpack_from(reply)
            out = np.frombuffer(reply, dtype=host.dtype,
                                offset=REPLY_TIMES.size).reshape(host.shape)
            t1 = time.perf_counter()
            span.interval(t0, t1)
            span.set(a0=len(payload))
            self._record_worker(worker, recv_s, echo_s)
        self._record(src_node, dst_node, len(payload), t1 - t0)
        self.moved_bytes += len(payload)
        return ShipResult(out, len(payload), t1 - t0, moved=True)

    def _record_worker(self, worker: int, recv_s: float,
                       echo_s: float) -> None:
        ws = self.worker_stats.setdefault(worker, WorkerStats())
        ws.n += 1
        ws.recv_s += recv_s
        ws.echo_s += echo_s
        if self._tracer.enabled:
            # The worker reports durations only (no shared clock); tail-
            # align against our receive time: the echo ended just before
            # the reply hit our socket, the drain just before the echo.
            tr = self._tracer
            now = tr.now()
            track = tr.track("transport_worker")
            tr.span(track, "worker_recv", now - echo_s - recv_s, recv_s,
                    lane=worker, a0=recv_s)
            tr.span(track, "worker_echo", now - echo_s, echo_s,
                    lane=worker, a0=echo_s)
