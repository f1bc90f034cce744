"""The program's spans in a benchmark run (``harness/program.py``,
``program_trace.py``): the idle-gap and unit reductions on a hand-built
trace and on the recorded v5e trace, the per-layer readers on a hand-built
record, and a traced run of the cell on the CPU at a small size."""

from pathlib import Path

import numpy as np
import pytest

import program_trace
from harness import program, trace
from test_cells import CELL, SEED, small  # noqa: F401  (autouse fixture)
from test_trace import Plane

DATA = Path(__file__).resolve().parent / "data"


def program_space():
    # window 100..1100; device busy 200..300, 500..600, 950..960 and
    # 1000..1100 (clipped), so the idle gaps are 100..200 (midpoint 150,
    # no program span open), 300..500 (400, in a launch after its
    # dispatch ended), 600..950 (775, in a ship) and 960..1000 (980, in
    # the run alone)
    space = trace.xplane.XSpace()
    host = Plane(space, "/host:CPU")
    host.line("python", [
        ("bench.window", 100, 1000, {}), ("bench.run", 150, 900, {}),
        ("repro.engine.run", 160, 830, {}),
        ("repro.engine.launch", 350, 100, {}),
        ("repro.engine.dispatch", 350, 10, {}),
        ("repro.transport.ship", 700, 200, {})])
    dev = Plane(space, "/device:TPU:0")
    dev.line("XLA Ops", [
        ("fusion.1", 200, 100,
         {"tf_op": "jit(_run)/unit3/conv_general_dilated:"}),
        ("fusion.2", 500, 100, {"tf_op": "jit(_run)/unit4/jit(relu)/max:"}),
        ("copy.3", 950, 10, {"tf_op": "jit(_run)/unit4/copy:"}),
        ("fusion.4", 1000, 200, {"tf_op": "jit(_run)/add:"})])
    return space


def test_program_gaps_and_unit_seconds():
    space = program_space()
    red = program.reduce(space)
    assert red["program_gap_s"] == pytest.approx({
        program.OUTSIDE: 100e-9, "engine.launch": 200e-9,
        "transport.ship": 350e-9, "engine.run": 40e-9})
    assert red["scope_s"] == pytest.approx({"unit3": 100e-9,
                                            "unit4": 110e-9})
    base = trace.reduce(space)
    assert sum(red["program_gap_s"].values()) == pytest.approx(
        base["window_s"] - base["busy_s"])


def test_innermost_takes_the_child_of_a_shared_start():
    spans = sorted([(0, 100, "engine.run"), (0, 50, "engine.upload")],
                   key=lambda sp: (sp[0], -sp[1]))
    assert program._innermost(spans, [10, 60, 200]) == [
        "engine.upload", "engine.run", program.OUTSIDE]


def test_v5e_trace_reductions():
    """The recorded v5e trace holds no program span: the reduction's fields
    are what they were, and every idle gap is outside program spans."""
    space = trace.load(str(DATA / "tiny_v5e.xplane.pb"))
    red = trace.reduce(space)
    assert red["chips"] == 1
    assert red["window_s"] == pytest.approx(0.01330241, abs=1e-12)
    assert red["busy_s"] == pytest.approx(3.4278e-05, abs=1e-12)
    assert len(red["op_s"]) == 12
    assert red["op_s"]["jit_conv_step/fusion.11"] == pytest.approx(
        1.2546e-05, abs=1e-12)
    assert red["category_s"]["convolution fusion"] == pytest.approx(
        1.9507e-05, abs=1e-12)
    assert red["primitive_s"] == pytest.approx({
        "": 6.2e-08, "x": 5.181e-06, "conv_general_dilated": 2.2074e-05,
        "dot_general": 6.961e-06}, abs=1e-12)
    assert red["module_s"] == pytest.approx(
        {"jit_conv_step": 2.7375e-05, "jit_sweep": 6.965e-06}, abs=1e-12)
    assert red["module_n"] == {"jit_conv_step": 3.0, "jit_sweep": 2.0}
    assert red["idle_gap_s"] == pytest.approx(
        {"between benchmark spans": 0.009844472, "solve": 0.00342366},
        abs=1e-12)
    prog = program.reduce(space)
    assert prog["scope_s"] == {}
    assert prog["program_gap_s"] == pytest.approx(
        {program.OUTSIDE: red["window_s"] - red["busy_s"]}, rel=1e-9)


def _spans(**cols):
    n = len(cols["ts"])
    out = {k: [0.0] * n for k in ("ts", "dur", "lane", "frame", "a0", "a1")}
    out.update(cols)
    return out


def hand_rec():
    # two rounds of 2 frames: runs at 1.0 and 2.0 s; two launches a round
    # (10 and 20 ms, dispatched in 1 and 2 ms); one gather and one split
    # of 1 ms a launch; frames done 30 and 40 ms into their run
    spans = {
        "engine.run": _spans(ts=[1.0, 2.0], dur=[0.05, 0.05]),
        "engine.launch": _spans(ts=[1.0, 1.01, 2.0, 2.01],
                                dur=[0.01, 0.02, 0.01, 0.02]),
        "engine.dispatch": _spans(ts=[1.0, 1.01, 2.0, 2.01],
                                  dur=[0.001, 0.002, 0.001, 0.002]),
        "engine.gather": _spans(ts=[1.0, 2.0], dur=[0.001, 0.001]),
        "engine.split": _spans(ts=[1.01, 1.03, 2.01, 2.03],
                               dur=[0.001] * 4),
        "engine.done": _spans(ts=[1.03, 1.04, 2.03, 2.04], dur=[-1.0] * 4,
                              frame=[0, 1, 0, 1]),
        "engine.fetch": _spans(ts=[1.04, 2.04], dur=[0.001] * 2,
                               a0=[80.0, 80.0]),
        "transport.ship": _spans(ts=[1.02, 2.02], dur=[0.003, 0.005],
                                 a0=[1000.0, 1000.0]),
        "solver.solve": _spans(ts=[0.9, 1.9], dur=[0.0004, 0.0006]),
        "admission.admit": _spans(ts=[0.9, 0.95, 1.9, 1.95],
                                  dur=[0.001, -1.0, 0.001, -1.0]),
    }
    return {"frames_done": 4, "program": {"spans": spans, "n_dropped": 0}}


def test_readers_on_a_hand_built_record():
    rec = hand_rec()
    got = {name: read(rec) for name, read in program.READERS.items()}
    assert got == pytest.approx({
        "dispatch_ms": 6e-3 / 4 * 1e3,
        "launch_wait_ms": (60e-3 - 6e-3) / 4 * 1e3,
        "reshape_ms": 6e-3 / 4 * 1e3,
        "frame_ready_ms": 35.0,
        "solve_ms.span": 0.5})
    assert program.d2h_bytes_per_frame(rec) == pytest.approx(540.0)
    per = program.per_round(rec)
    assert per["transport.ship"] == pytest.approx([0.004, 0.0049])
    assert per["engine.run"] == pytest.approx([0.05, 0.05])
    assert "engine.done" not in per
    for read in (*program.READERS.values(), program.d2h_bytes_per_frame):
        assert read({"frames_done": 4, "trace": None}) is None


def test_a_traced_run_on_the_cpu():
    """A run of the cell with the program's tracer attached: the launch
    spans add up to the stage walls and the ship spans to the transfer
    walls, frames are ready within their round, the ring lost nothing, and
    the run is as correct as an untraced one."""
    from repro.exec import ExecutionEngine
    from repro.runtime.serve import AdmissionController

    res, rec = program_trace.run_traced(CELL, SEED, 0.5, tracing=False)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(program.READERS) <= set(m)
    n = rec["frames_done"]
    assert m["dispatch_ms"] + m["launch_wait_ms"] == pytest.approx(
        rec["stage_s"] / n * 1e3, rel=1e-9)
    ships = rec["program"]["spans"]["transport.ship"]["dur"]
    assert sum(ships) == pytest.approx(rec["transfer_s"], rel=1e-9)
    assert 0 < m["frame_ready_ms"] <= np.mean(rec["frame_latencies_s"]) * 1e3
    assert res["program"]["n_dropped"] == 0
    assert res["program"]["rounds"] * 4 == len(rec["frame_latencies_s"])
    assert res["program"]["d2h_bytes_per_frame"] > 0
    # the program's classes are its own again
    assert ExecutionEngine.__name__ == "ExecutionEngine"
    assert AdmissionController.__name__ == "AdmissionController"
