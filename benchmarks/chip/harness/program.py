"""The program's own spans in a run: what ``repro.obs.Tracer.scope`` records
inside the engine, the transport and admission, and the same spans as
``repro.<track>.<name>`` host events of a profiler trace.

* :func:`window`: the ring spans appended between two ``Tracer.seq``
  readings (the measured window), per ``<track>.<name>``;
* :func:`reduce`: over a trace's ``bench.window``, the device's idle time
  named by the innermost program span open at each gap's midpoint, and the
  device seconds of each CNN unit (``unit<i>`` in an op's ``tf_op``);
* the per-layer numbers read from :func:`window`'s spans, one function each
  (``None`` where the record holds no program spans).
"""

from __future__ import annotations

import collections
import re

import numpy as np

from . import trace

PREFIX = "repro."
OUTSIDE = "outside program spans"
_UNIT = re.compile(r"(?:^|/)(unit\d+)(?:/|$)")


def window(tracer, since: int, until: int) -> dict:
    """Spans and instants with sequence numbers in ``[since, until)``, per
    ``<track>.<name>``, in the order they were written (a span when it
    ended); ``n_dropped`` counts the window's events the ring lost."""
    ev = tracer.events()
    first = tracer.seq - tracer.n_events          # seq of ev[0]
    lo, hi = max(since - first, 0), max(until - first, 0)
    out: dict[str, dict[str, list]] = {}
    for i in range(lo, hi):
        key = f"{ev['track'][i]}.{ev['name'][i]}"
        d = out.setdefault(key, {k: [] for k in
                                 ("ts", "dur", "lane", "frame", "a0", "a1")})
        for k in d:
            d[k].append(ev[k][i].item())
    return {"spans": out, "n_dropped": max(first - since, 0)}


def _col(rec, name, col="dur"):
    return np.asarray(rec["program"]["spans"].get(name, {}).get(col, []),
                      float)


def _per_frame_ms(rec, total_s):
    n = rec.get("frames_done")
    return total_s / n * 1e3 if n else None


def dispatch_ms(rec):
    """Host seconds enqueuing stage launches (``engine.dispatch``), per
    frame."""
    if "program" not in rec:
        return None
    return _per_frame_ms(rec, _col(rec, "engine.dispatch").sum())


def launch_wait_ms(rec):
    """Host seconds blocked on launched stages (each ``engine.launch`` less
    its ``engine.dispatch``), per frame."""
    if "program" not in rec:
        return None
    return _per_frame_ms(rec, _col(rec, "engine.launch").sum()
                         - _col(rec, "engine.dispatch").sum())


def reshape_ms(rec):
    """Host seconds batching requests into launches and splitting launch
    outputs back into rows (``engine.gather`` + ``engine.split``), per
    frame."""
    if "program" not in rec:
        return None
    return _per_frame_ms(rec, _col(rec, "engine.gather").sum()
                         + _col(rec, "engine.split").sum())


def frame_ready_ms(rec):
    """Mean over frames of ``engine.done`` (the return of a request's last
    launch) less the start of its ``engine.run``."""
    if "program" not in rec:
        return None
    runs = np.sort(_col(rec, "engine.run", "ts"))
    done = _col(rec, "engine.done", "ts")
    if not runs.size or not done.size:
        return None
    start = runs[np.searchsorted(runs, done, side="right") - 1]
    return float(np.mean(done - start)) * 1e3


def solve_ms_span(rec):
    """Mean ``solver.solve`` span (the planner call inside admission) per
    round."""
    if "program" not in rec:
        return None
    solve = _col(rec, "solver.solve")
    return float(solve.mean()) * 1e3 if solve.size else None


READERS = {"dispatch_ms": dispatch_ms, "launch_wait_ms": launch_wait_ms,
           "reshape_ms": reshape_ms, "frame_ready_ms": frame_ready_ms,
           "solve_ms.span": solve_ms_span}


def per_round(rec) -> dict[str, list[float]]:
    """p50 and p95 over the window's rounds of each span's seconds per
    round, a round running from one ``admission.admit`` start to the
    next."""
    adm = rec["program"]["spans"].get("admission.admit", {"ts": [],
                                                          "dur": []})
    starts = np.sort([t for t, d in zip(adm["ts"], adm["dur"]) if d >= 0])
    if not starts.size:
        return {}
    out = {}
    for name, d in rec["program"]["spans"].items():
        dur = np.asarray(d["dur"], float)
        if (dur < 0).all():
            continue                                    # instants
        ts = np.asarray(d["ts"], float)[dur >= 0]
        idx = np.searchsorted(starts, ts, side="right") - 1
        keep = idx >= 0
        tot = np.bincount(idx[keep], dur[dur >= 0][keep],
                          minlength=starts.size)
        out[name] = [float(np.percentile(tot, 50)),
                     float(np.percentile(tot, 95))]
    return out


def d2h_bytes_per_frame(rec):
    """Bytes a frame moves device-to-host: the transfers' (``ship``) and
    the outputs' (``fetch``) payloads, per frame."""
    n = rec.get("frames_done")
    if "program" not in rec or not n:
        return None
    return float(_col(rec, "transport.ship", "a0").sum()
                 + _col(rec, "engine.fetch", "a0").sum()) / n


def reduce(space) -> dict:
    """``program_gap_s``: the window's device idle time by the innermost
    program span open at each gap's midpoint (``engine.run`` where only
    the run itself is open, :data:`OUTSIDE` where none is); ``scope_s``:
    device seconds per CNN unit; both averaged over chips."""
    spans, wins = [], []
    for plane in space.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for s, e, md in trace._events(plane, line):
                    if md.name.startswith(PREFIX):
                        spans.append((s, e, md.name[len(PREFIX):]))
                    elif md.name == trace.WINDOW:
                        wins.append((s, e))
    if len(wins) != 1:
        raise RuntimeError(f"expected one {trace.WINDOW} span, found "
                           f"{len(wins)}")
    lo, hi = wins[0]
    spans.sort(key=lambda sp: (sp[0], -sp[1]))      # a parent before its child
    chips = [p for p in space.planes if p.name.startswith("/device:TPU:")
             and p.name[len("/device:TPU:"):].isdigit()]
    if not chips:
        raise RuntimeError("the trace holds no TPU device plane")
    gap, unit = collections.Counter(), collections.Counter()
    for plane in chips:
        ops, _ = trace._device(plane, lo, hi)
        for s, e, _name, _cat, tf_op in ops:
            m = _UNIT.search(tf_op)
            if m:
                unit[m.group(1)] += e - s
        busy = trace._union([(s, e) for s, e, *_ in ops])
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = [(gs, ge) for gs, ge in zip(edges[::2], edges[1::2])
                if ge > gs]
        names = _innermost(spans, [(gs + ge) // 2 for gs, ge in gaps])
        for (gs, ge), name in zip(gaps, names):
            gap[name] += ge - gs
    sec = 1e-9 / len(chips)
    return {"program_gap_s": {k: v * sec for k, v in gap.items()},
            "scope_s": {k: v * sec for k, v in
                        sorted(unit.items(), key=lambda kv: int(kv[0][4:]))}}


def _innermost(spans, times) -> list[str]:
    """For each of ``times`` (ascending), the name of the innermost span
    open at it.  The program's spans come from one thread, so they nest:
    one sweep with a stack of the open spans finds them all."""
    out, stack, i = [], [], 0
    for t in times:
        while i < len(spans) and spans[i][0] <= t:
            while stack and stack[-1][1] < spans[i][0]:
                stack.pop()
            stack.append(spans[i])
            i += 1
        while stack and stack[-1][1] < t:
            stack.pop()
        out.append(stack[-1][2] if stack else OUTSIDE)
    return out
