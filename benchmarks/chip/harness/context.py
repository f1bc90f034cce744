"""What a driver gets from the runner: its cell's files, the seed and the
window, the device and its peaks, host spans, compile events and the
profiler."""

from __future__ import annotations

import dataclasses
import shutil
import tempfile
import time
from typing import Any

from . import runtime, trace


@dataclasses.dataclass
class Context:
    seed: int
    seconds: float
    tracing: bool
    config: dict
    traffic: dict
    devices: list
    peaks: dict
    t_start: float                      # process start, perf_counter
    compiles: runtime.CompileLog
    spans: runtime.Spans = None
    setup_s: float | None = None
    memory_peak_bytes: int | None = None
    trace: dict | None = None
    _trace_dir: str | None = None

    def __post_init__(self):
        self.spans = runtime.Spans(annotate=self.tracing)

    # -- phases ---------------------------------------------------------------

    def setup_done(self) -> None:
        """Set-up ends here: every shape the window uses is warm."""
        self.setup_s = time.perf_counter() - self.t_start
        self.spans.secs.clear()
        runtime.log(f"[setup] setup_s={self.setup_s:.3f} "
                    + self.compiles.line("setup"))
        self.compiles.phase = "window"
        if self.tracing:
            import jax
            self._trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # no Python call events
            opts.host_tracer_level = 1     # the benchmark's own spans
            jax.profiler.start_trace(self._trace_dir, profiler_options=opts)

    def window_closed(self) -> None:
        """The measured window is over: stop the profiler, read the peak
        memory before anything else runs on the device."""
        if self.tracing:
            import jax
            jax.profiler.stop_trace()
        self.memory_peak_bytes = max(
            int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in self.devices)
        runtime.log(f"[window] {self.compiles.line('window')} "
                    f"memory_peak_bytes={self.memory_peak_bytes}")
        self.compiles.phase = "after"
        if self.tracing:
            t0 = time.perf_counter()
            path = trace.find_xplane(self._trace_dir)
            self.trace = trace.reduce(trace.load(path))
            shutil.rmtree(self._trace_dir, ignore_errors=True)
            runtime.log(f"[trace] window_s={self.trace['window_s']:.6f} "
                        f"busy_s={self.trace['busy_s']:.6f} "
                        f"reduced in {time.perf_counter() - t0:.3f}s")

    def window(self):
        """The span that marks the measured window in the trace."""
        return self.spans("window")

    def record(self, **rec: Any) -> dict:
        rec.setdefault("setup_s", self.setup_s)
        rec["trace"] = self.trace
        rec["peaks"] = self.peaks
        rec["spans"] = dict(self.spans.secs)
        return rec
