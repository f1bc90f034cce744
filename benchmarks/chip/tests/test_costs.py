"""The benchmark's own operation and byte counts."""

import json

import pytest

from harness import costs, runtime


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


def test_conv_bound_takes_the_larger_bound():
    cfg = config("vgg16")
    conv0 = costs.units(cfg)[0].ops[0]
    flops_s, bytes_s = 197e12, 819e9
    # 3 -> 64 channels: far more bytes than FLOPs per second of peak; the
    # bytes are counted at bf16 width.
    want = (conv0.in_bytes + conv0.out_bytes + conv0.param_bytes) / 2
    assert costs.conv_bound_s(cfg, 0, 1, 1, flops_s, bytes_s) == want / bytes_s
    # A batch reads the weights once.
    two = costs.conv_bound_s(cfg, 0, 1, 2, flops_s, bytes_s)
    assert two == (2 * (conv0.in_bytes + conv0.out_bytes)
                   + conv0.param_bytes) / 2 / bytes_s
    # 512 -> 512 channels at 40x74: bound by FLOPs.
    conv10 = costs.units(cfg)[10].ops[0]
    assert costs.conv_bound_s(cfg, 10, 11, 1, flops_s, bytes_s) == (
        conv10.flops / flops_s)
    # Pool units hold no convolution.
    assert costs.conv_bound_s(cfg, 2, 3, 1, flops_s, bytes_s) == 0.0


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
