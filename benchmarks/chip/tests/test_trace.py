"""The trace reduction: on a hand-built trace whose answers are known, and
on a small trace recorded on a TPU v5e chip (``tests/data``)."""

from pathlib import Path

import pytest

from harness import trace

DATA = Path(__file__).resolve().parent / "data"


class Plane:
    """Builds one XPlane of a hand-made trace."""

    def __init__(self, space, name):
        self.p = space.planes.add(name=name)
        self.stat_ids: dict[str, int] = {}

    def line(self, name, events, timestamp_ns=0):
        ln = self.p.lines.add(name=name, timestamp_ns=timestamp_ns)
        for name_, start, dur, stats in events:
            mid = len(self.p.event_metadata) + 1
            md = self.p.event_metadata[mid]
            md.id, md.name = mid, name_
            for k, v in stats.items():
                sid = self.stat_ids.setdefault(k, len(self.stat_ids) + 1)
                self.p.stat_metadata[sid].name = k
                md.stats.add(metadata_id=sid, str_value=v)
            ln.events.add(metadata_id=mid, offset_ps=(start - timestamp_ns)
                          * 1000, duration_ps=dur * 1000)


def hand_trace(window=True):
    # window 100..1100 ns; ops overlap at 200..300 and 250..400; one op
    # straddles the window's end; idle gaps at 100..200, 400..800 (inside
    # bench.run 300..900) and 900..1000 (between spans)
    space = trace.xplane.XSpace()
    Plane(space, "/host:metadata")
    host = Plane(space, "/host:CPU")
    host.line("python", ([("bench.window", 100, 1000, {})] if window else [])
              + [("bench.run", 300, 600, {}), ("other", 0, 5000, {})],
              timestamp_ns=50)
    dev = Plane(space, "/device:TPU:0")
    conv = {"hlo_category": "convolution fusion",
            "tf_op": "jit(_run)/conv_general_dilated:"}
    dev.line("XLA Modules", [("jit_a(1)", 200, 200, {}),
                             ("jit_sweep(7)", 800, 100, {}),
                             ("jit_a(1)", 1000, 300, {})])
    dev.line("XLA Ops", [
        ("fusion.1", 200, 100, conv),
        ("fusion.2", 250, 150, {"hlo_category": "loop fusion",
                                "tf_op": "jit(_run)/max:"}),
        ("copy.3", 800, 100, {"hlo_category": "data formatting"}),
        ("fusion.4", 1000, 300, conv)])
    Plane(space, "/device:TPU:0 SparseCore 0").line(
        "XLA Ops", [("x", 100, 1000, {})])
    return space


def test_hand_built_trace():
    red = trace.reduce(hand_trace())
    assert red["chips"] == 1
    assert red["window_s"] == pytest.approx(1000e-9)
    # union of 200..400, 800..900 and 1000..1100 (clipped)
    assert red["busy_s"] == pytest.approx(400e-9)
    assert red["category_s"]["convolution fusion"] == pytest.approx(200e-9)
    assert red["primitive_s"]["conv_general_dilated"] == pytest.approx(
        200e-9)
    assert red["module_s"] == pytest.approx({"jit_a": 300e-9,
                                             "jit_sweep": 100e-9})
    assert red["module_n"] == {"jit_a": 2, "jit_sweep": 1}
    assert red["idle_gap_s"] == pytest.approx(
        {"between benchmark spans": 200e-9, "run": 400e-9})
    bd = trace.breakdown(red, top=2)
    assert bd["device_ops"][0] == ["jit_a/fusion.2", pytest.approx(150e-9)]
    assert len(bd["device_ops"]) == 2
    assert bd["idle_gaps"][0][0] == "run"


def test_conv_roofline_reads_its_kernel_bound_over_conv_time():
    from harness import runtime
    reader = runtime.load_module(runtime.CHIP_DIR / "metrics"
                                 / "conv_roofline.py")
    red = trace.reduce(hand_trace())
    assert reader.read({"trace": red, "bound_s": {"conv": 50e-9}}) == (
        pytest.approx(25.0))
    # A window with no convolution to bound reads nothing, not 0.
    assert reader.read({"trace": red, "bound_s": {"other": 1e-9}}) is None


def test_a_trace_without_a_window_is_refused():
    with pytest.raises(RuntimeError, match="bench.window"):
        trace.reduce(hand_trace(window=False))


def test_v5e_trace_agrees_with_jax_reader():
    """A trace recorded on one v5e chip: three rounds of a jitted sweep (in
    ``bench.solve``) and a jitted convolution (in ``bench.run``) inside
    ``bench.window``.  Busy time must equal the union of the ``XLA Ops``
    intervals that JAX's own reader gives."""
    import jax

    path = str(DATA / "tiny_v5e.xplane.pb")
    red = trace.reduce(trace.load(path))
    pd = jax.profiler.ProfileData.from_file(path)
    host = [e for p in pd.planes if p.name == "/host:CPU"
            for ln in p.lines for e in ln.events]
    win = next(e for e in host if e.name == "bench.window")
    lo, hi = win.start_ns, win.start_ns + win.duration_ns
    ops = [(max(e.start_ns, lo), min(e.start_ns + e.duration_ns, hi))
           for p in pd.planes if p.name == "/device:TPU:0"
           for ln in p.lines if ln.name == "XLA Ops" for e in ln.events]
    busy = trace._union([(s, e) for s, e in ops if e > s])
    assert red["busy_s"] == pytest.approx(
        sum(e - s for s, e in busy) * 1e-9, abs=2e-9 * len(busy))
    assert red["window_s"] == pytest.approx(win.duration_ns * 1e-9,
                                            abs=1e-9)
    assert 0 < red["busy_s"] < red["window_s"]
    assert red["primitive_s"]["conv_general_dilated"] > 0
    assert red["primitive_s"]["dot_general"] > 0
    assert red["module_n"]["jit_conv_step"] == 3
    assert set(red["idle_gap_s"]) <= {"solve", "run",
                                      "between benchmark spans"}
    assert sum(red["idle_gap_s"].values()) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-6)


def test_v5e_pallas_kernel_lands_under_pallas_call():
    """A trace recorded on one v5e chip by ``record_attention_trace.py``:
    one launch of the program's non-causal Pallas flash attention at
    ViT-B/16's shape (``bench.attention``), then the same attention as
    plain XLA ops (``bench.plain``), inside ``bench.window``.  The kernel
    is one custom-call op whose time ``reduce`` keeps whole under the
    primitive ``pallas_call``, named in ``op_s`` after the jitted function
    that launched it; the plain attention's matmuls land under
    ``dot_general`` and none of its time under ``pallas_call``."""
    red = trace.reduce(trace.load(str(DATA / "flash_attention_v5e.xplane.pb")))
    kernel = red["primitive_s"]["pallas_call"]
    assert red["op_s"]["jit_vit_attention/vit_attention.1"] == kernel
    assert red["category_s"]["custom-call"] == pytest.approx(kernel,
                                                             abs=1e-8)
    assert red["primitive_s"]["dot_general"] > 0
    # The launch's whole program, kernel and the transposes and pads around
    # it, inside the window: nothing clipped.
    ours = sum(v for k, v in red["op_s"].items()
               if k.startswith("jit_vit_attention/"))
    assert kernel < ours <= red["module_s"]["jit_vit_attention"]
    assert red["module_n"] == {"jit_vit_attention": 1, "jit_plain": 1}
