"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

Device planes are those named ``/device:TPU:<n>``.  Their ``XLA Ops`` line
holds one event per executed HLO op, whose metadata names its HLO category
and the JAX primitive it came from (``tf_op``); their ``XLA Modules`` line
holds one event per executed program.  Host spans the benchmark wrote with
``TraceAnnotation`` are named ``bench.<name>``; the one named
``bench.window`` marks the measured window, and every device number here is
clipped to it.  (Host and device events share the profiler's clock; on a
v5e the two were seen up to about a millisecond apart.)

* busy: the union of the op intervals of a chip inside the window;
* device time per op, per HLO category, per JAX primitive and per program;
* idle gaps named by the innermost benchmark span open at their midpoint.
"""

from __future__ import annotations

import bisect
import collections
import glob

from . import xplane

WINDOW = "bench.window"


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {paths}")
    return paths[0]


def load(path: str):
    return xplane.load(path)


def _events(plane, line):
    """(start_ns, end_ns, metadata) of every event of a line."""
    t0 = line.timestamp_ns
    meta = plane.event_metadata
    for ev in line.events:
        s = t0 + ev.offset_ps // 1000
        yield s, s + ev.duration_ps // 1000, meta[ev.metadata_id]


def _stats(plane, md) -> dict:
    names = plane.stat_metadata
    out = {}
    for st in md.stats:
        kind = st.WhichOneof("value")
        if kind is not None:
            out[names[st.metadata_id].name] = getattr(st, kind)
    return out


def _union(intervals):
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _device(plane, lo, hi):
    """Clipped op records and program intervals of one chip."""
    ops, mods = [], []
    kinds: dict[int, tuple[str, str, str]] = {}
    for line in plane.lines:
        if line.name == "XLA Modules":
            mods = sorted((s, e, md.name.split("(")[0])
                          for s, e, md in _events(plane, line))
        elif line.name == "XLA Ops":
            for s, e, md in _events(plane, line):
                s, e = max(s, lo), min(e, hi)
                if e <= s:
                    continue
                if md.id not in kinds:
                    st = _stats(plane, md)
                    kinds[md.id] = (md.display_name or md.name,
                                    str(st.get("hlo_category", "")),
                                    str(st.get("tf_op", "")))
                ops.append((s, e) + kinds[md.id])
    return ops, mods


def reduce(space) -> dict:
    """Device busy, idle gaps and device seconds per op, HLO category, JAX
    primitive and program over the ``bench.window`` span, averaged over
    chips."""
    spans = []
    for plane in space.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for s, e, md in _events(plane, line):
                    if md.name.startswith("bench."):
                        spans.append((s, e, md.name))
    wins = [(s, e) for s, e, n in spans if n == WINDOW]
    if len(wins) != 1:
        raise RuntimeError(f"expected one {WINDOW} span, found {len(wins)}")
    lo, hi = wins[0]
    inner = sorted((s, e, n) for s, e, n in spans if n != WINDOW)
    chips = [p for p in space.planes if p.name.startswith("/device:TPU:")
             and p.name[len("/device:TPU:"):].isdigit()]
    if not chips:
        raise RuntimeError("the trace holds no TPU device plane")

    by = {k: collections.Counter()
          for k in ("op", "category", "primitive", "module", "module_n",
                    "gap")}
    busy_ns = 0
    for plane in chips:
        ops, mods = _device(plane, lo, hi)
        starts = [m[0] for m in mods]
        for s, e, name, cat, prim in ops:
            i = bisect.bisect_right(starts, s) - 1
            mod = mods[i][2] if i >= 0 and mods[i][1] >= e else ""
            d = e - s
            by["op"][f"{mod}/{name}" if mod else name] += d
            by["category"][cat] += d
            by["primitive"][prim.split("/")[-1].rstrip(":")] += d
        for s, e, name in mods:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                by["module"][name] += e - s
                by["module_n"][name] += 1
        busy = _union([(s, e) for s, e, *_ in ops])
        busy_ns += sum(e - s for s, e in busy)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge > gs:
                by["gap"][_innermost(inner, (gs + ge) // 2)] += ge - gs
    n = len(chips)
    sec = 1e-9 / n
    return {
        "chips": n,
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_ns * sec,
        "op_s": {k: v * sec for k, v in by["op"].items()},
        "category_s": {k: v * sec for k, v in by["category"].items()},
        "primitive_s": {k: v * sec for k, v in by["primitive"].items()},
        "module_s": {k: v * sec for k, v in by["module"].items()},
        "module_n": {k: v / n for k, v in by["module_n"].items()},
        "idle_gap_s": {k: v * sec for k, v in by["gap"].items()},
    }


def _innermost(spans, t: int) -> str:
    best = None
    for s, e, name in spans:
        if s > t:
            break
        if e >= t and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2][len("bench."):] if best else "between benchmark spans"


def breakdown(red: dict, top: int = 10) -> dict:
    """The ``breakdown`` of the result line: the device ops that took most
    time and the idle time by what the host was doing."""
    def head(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                [:top]]
    return {"device_ops": head(red["op_s"]),
            "idle_gaps": head(red["idle_gap_s"])}
