"""The benchmark's own operation and byte counts."""

import json
import shutil

import pytest

from harness import costs, runtime


# Recorded from the counts as they stood before each op kind moved to a file
# of its own (``ops/<kind>.py``): per unit (memory_bytes, compute_flops,
# output_bytes), and the convolutions' bound in seconds of units
# [start, end) at a batch, at the v5e's peaks.  The placement and the
# rooflines of every earlier ledger line rest on them.
UNITS = {
    "vgg16": [
        (51991128, 670360320.0, 49656320),
        (99460352, 14301020160.0, 49656320),
        (62049536, 12393216.0, 12393216),
        (37475072, 7138492416.0, 24786432),
        (50163200, 14276984832.0, 24786432),
        (30924288, 6137856.0, 6137856),
        (19594240, 7070810112.0, 12275712),
        (26911744, 14141620224.0, 12275712),
        (26911744, 14141620224.0, 12275712),
        (15306752, 3031040.0, 3031040),
        (13813760, 6983516160.0, 6062080),
        (21563392, 13967032320.0, 6062080),
        (21563392, 13967032320.0, 6062080),
        (7577600, 1515520.0, 1515520),
        (12470272, 3491758080.0, 1515520),
        (12470272, 3491758080.0, 1515520),
        (12470272, 3491758080.0, 1515520),
        (496625472.0, 247635968.0, 4000),
    ],
    "lenet": [
        (6896712, 171271800.0, 4567248),
        (5707128, 1139880.0, 1139880),
        (4073512, 219297600.0, 2923968),
        (3647808.0, 723840.0, 723840),
        (87585600, 43430400.0, 480),
        (41472, 20160.0, 336),
        (3776, 1680.0, 40),
    ],
}
CONV_BOUND_S = [
    (0, 1, 1, 3.174061538461539e-05),
    (0, 1, 2, 6.34768547008547e-05),
    (10, 11, 1, 3.544932060913706e-05),
    (10, 11, 2, 7.089864121827411e-05),
    (2, 3, 1, 0.0),
    (2, 3, 2, 0.0),
    (0, 18, 1, 0.0006229254022272548),
    (0, 18, 2, 0.0012458464283861337),
]
V5E = (197e12, 819e9)


def config(name):
    """The cell's VGG16, and the LeNet the CPU tests run at a small size."""
    path = (runtime.CHIP_DIR / "tests" / "data" if name == "lenet"
            else runtime.CHIP_DIR / "configs") / f"{name}.json"
    return runtime.load_json(path)


@pytest.mark.parametrize("name,gflop", [("vgg16", 117.40), ("lenet", 0.436)])
def test_frame_flops(name, gflop):
    assert costs.frame_flops(config(name)) / 1e9 == pytest.approx(
        gflop, abs=5e-3)


@pytest.mark.parametrize("name", ["vgg16", "lenet"])
def test_units_match_the_program_profile(name):
    from repro.core import lenet_profile, vgg16_profile
    prof = (vgg16_profile(num_classes=config(name)["num_classes"])
            if name == "vgg16" else lenet_profile())
    units = costs.units(config(name))
    assert len(units) == prof.num_layers
    for u, ly in zip(units, prof.layers):
        assert (u.memory_bytes, u.compute_flops, u.output_bytes) == (
            ly.memory_bytes, ly.compute_flops, ly.output_bytes), ly.name
    assert costs.input_bytes(config(name)) == prof.input_bytes


@pytest.mark.parametrize("name", ["vgg16", "lenet"])
def test_units_are_the_recorded_counts(name):
    assert [(u.memory_bytes, u.compute_flops, u.output_bytes)
            for u in costs.units(config(name))] == UNITS[name]


@pytest.mark.parametrize("start,end,batch,want", CONV_BOUND_S)
def test_conv_bound_is_the_recorded_one(start, end, batch, want):
    got = costs.bound_s(config("vgg16"), start, end, batch, *V5E)
    assert got.get("conv", 0.0) == want
    assert set(got) <= {"conv"}


def test_conv_bound_takes_the_larger_bound():
    cfg = config("vgg16")
    conv0 = costs.units(cfg)[0].ops[0]
    flops_s, bytes_s = V5E
    # 3 -> 64 channels: far more bytes than FLOPs per second of peak; the
    # bytes are counted at bf16 width.
    want = (conv0.in_bytes + conv0.out_bytes + conv0.param_bytes) / 2
    assert costs.bound_s(cfg, 0, 1, 1, flops_s, bytes_s) == {
        "conv": want / bytes_s}
    # A batch reads the weights once.
    two = costs.bound_s(cfg, 0, 1, 2, flops_s, bytes_s)["conv"]
    assert two == (2 * (conv0.in_bytes + conv0.out_bytes)
                   + conv0.param_bytes) / 2 / bytes_s
    # 512 -> 512 channels at 40x74: bound by FLOPs.
    conv10 = costs.units(cfg)[10].ops[0]
    assert costs.bound_s(cfg, 10, 11, 1, flops_s, bytes_s)["conv"] == (
        conv10.flops / flops_s)
    # Pool units hold no kernel.
    assert costs.bound_s(cfg, 2, 3, 1, flops_s, bytes_s) == {}


PATCHIFY = """\
from harness.costs import F32, OpCost


def cost(shape, op):
    h, w, c = shape
    p, d = op["patch"], op["d"]
    n = (h // p) * (w // p)
    params = (p * p * c + 1) * d * F32
    return (OpCost(2.0 * n * p * p * c * d, h * w * c * F32, n * d * F32,
                   params, params + n * d * F32, kernel="patch_embed"),
            (n, d))
"""
MEAN_TOKENS = """\
from harness.costs import F32, OpCost


def cost(shape, op):
    n, d = shape
    return OpCost(1.0 * n * d, n * d * F32, d * F32, 0.0, 0.0), (1, 1, d)
"""


def test_an_op_kind_is_added_as_a_file(tmp_path, monkeypatch):
    """A token-shaped kind and its own kernel, in an ``ops/`` directory of
    their own beside copies of the shipped kinds, are costed by name: an
    image to (tokens, d), back to a vector that ``dense`` takes."""
    ops = tmp_path / "ops"
    shutil.copytree(costs.OPS_DIR, ops)
    (ops / "patchify.py").write_text(PATCHIFY)
    (ops / "mean_tokens.py").write_text(MEAN_TOKENS)
    monkeypatch.setattr(costs, "OPS_DIR", ops)
    cfg = {"name": "tiny_vit", "frame": [32, 48, 3], "units": [
        [{"op": "patchify", "patch": 16, "d": 8}],
        [{"op": "mean_tokens"}, {"op": "dense", "out": 10}]]}
    patch, head = costs.units(cfg)
    n = 2 * 3
    assert patch.compute_flops == 2.0 * n * 16 * 16 * 3 * 8
    assert patch.output_bytes == n * 8 * 4
    assert head.compute_flops == n * 8 + 2.0 * 8 * 10
    assert head.output_bytes == 10 * 4
    bound = costs.bound_s(cfg, 0, 2, 3, *V5E)
    assert set(bound) == {"patch_embed"}
    assert bound["patch_embed"] == max(
        3 * patch.compute_flops / V5E[0],
        (3 * (32 * 48 * 3 + n * 8) * 4 + (16 * 16 * 3 + 1) * 8 * 4) / 2
        / V5E[1])
    assert costs.program_profile(cfg).num_layers == 2


def test_an_unknown_op_kind_names_itself_and_the_ops_directory():
    cfg = {"name": "bad", "frame": [8, 8, 3], "units": [[{"op": "nope"}]]}
    with pytest.raises(ValueError, match=r"'nope'.*nope\.py.*ops"):
        costs.units(cfg)


def test_reference_param_shapes_match_the_program():
    import jax
    from repro.models import cnn
    from references import cnn as ref
    for name, init in (("vgg16", cnn.vgg16_init), ("lenet", cnn.lenet_init)):
        want = jax.eval_shape(lambda k: init(
            k, num_classes=config(name)["num_classes"]), jax.random.PRNGKey(0))
        got = {k: (s, f) for k, (s, f) in ref.param_shapes(config(name))
               .items()}
        assert sorted(got) == sorted(want)
        for k, (shape, _) in got.items():
            assert want[k]["w"].shape == shape, k


def test_peaks_refuse_an_unknown_device():
    from harness import peaks
    assert peaks.peaks_for("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
    json.dumps(peaks.PEAKS)
