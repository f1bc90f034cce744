"""In-process transport: the modeled-delay path, bit-compatible default.

This is exactly what the pre-transport engine did per transfer — block until
the producer's activation is ready, copy it to host (the observable
serialization cost of a U2U shipment on this substrate), and hand the
*original* device array to the consuming stage.  No bytes leave the process;
the link delay stays the analytic ``nbytes × spb`` term the planner priced.
Keeping the returned array identical to the input is what makes an engine
with the default transport bitwise-equal to the pre-transport engine.
"""

from __future__ import annotations

import time

import jax
import numpy as np

from ..obs import TRANSPORT
from .base import ShipResult, TransportBase


class InProcTransport(TransportBase):
    name = "inproc"

    def ship(self, src_node: int, dst_node: int, array) -> ShipResult:
        with self._tracer.scope(TRANSPORT, "ship", lane=src_node) as span:
            t0 = time.perf_counter()
            host = np.asarray(jax.block_until_ready(array))
            t1 = time.perf_counter()
            span.interval(t0, t1)
            span.set(a0=host.nbytes)
        self._record(src_node, dst_node, host.nbytes, t1 - t0)
        return ShipResult(array, int(host.nbytes), t1 - t0, moved=False)
