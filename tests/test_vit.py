"""ViT-B/16 as placement units (``repro.models.vit``): the unit callables
against the benchmark's plain reference at a tiny width, under both kernel
backends and over every three-way split of the units, the placed path end
to end, and the profile against the benchmark's own counts."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SnapshotView, vitb16_profile
from repro.exec import ExecutionEngine, compile_plan, layer_fns_for
from repro.kernels import ops
from repro.launch.serve import NODE_MEM_BYTES
from repro.models import cnn, vit
from repro.runtime.serve import AdmissionController

CHIP = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
SEED = 2**31 + 77


@pytest.fixture(scope="module")
def chip():
    """The benchmark's harness: its costs, its ViT reference and its
    camera-round driver's deployment."""
    sys.path.insert(0, str(CHIP))
    from harness import costs, runtime
    return {"costs": costs, "runtime": runtime,
            "ref": runtime.reference("vit"),
            "driver": runtime.load_module(CHIP / "drivers"
                                          / "camera_rounds.py")}


@pytest.fixture(scope="module")
def tiny(chip):
    """d 32, 2 heads, 2 blocks, MLP 64, 10 classes on a 32x48 frame with
    16x16 patches: 7 tokens; weights from the reference's seeded draw."""
    cfg = chip["runtime"].load_json(CHIP / "tests" / "data" / "vit_tiny.json")
    return cfg, chip["ref"].init(cfg, SEED)


@pytest.fixture(params=["xla", "pallas"])
def kernels(request, monkeypatch):
    """The kernel backend: jnp attention, or the Pallas flash kernel
    (interpret mode off the TPU)."""
    monkeypatch.setenv("REPRO_KERNELS", request.param)
    ops._mode.cache_clear()
    yield request.param
    ops._mode.cache_clear()


def _frames(n=3, seed=0):
    return np.random.default_rng(seed).standard_normal(
        (n, 32, 48, 3)).astype(np.float32)


def _rel(got, want):
    return float(np.abs(np.asarray(got) - want).max() / np.abs(want).max())


@pytest.mark.parametrize("s,e", [(1, 2), (1, 3), (2, 3)])
def test_units_match_the_plain_forward_over_every_split(chip, tiny, kernels,
                                                        s, e):
    """[0, s), [s, e), [e, M) through ``apply_layers``, each fed the
    previous range's output, equal the reference range by range and end to
    end."""
    cfg, params = tiny
    ref = chip["ref"]
    fns = vit.vitb16_layers(params)
    assert len(fns) == len(cfg["units"]) == 4
    x = _frames()
    act = x
    for a, b in ((0, s), (s, e), (e, len(fns))):
        run = jax.jit(lambda y, a=a, b=b: cnn.apply_layers(fns, y, a, b))
        got = np.asarray(run(jnp.asarray(act)))
        want = ref.forward(cfg, params, act, "float32", a, b)
        assert got.shape == want.shape
        assert _rel(got, want) < 1e-5, (a, b)
        act = got
    assert _rel(act, ref.forward(cfg, params, x)) < 1e-5


def test_attention_takes_the_kernel_path(tiny, kernels):
    """Each block's attention is one ``pallas_call`` under the Pallas
    backend and none under XLA; nothing else is a Pallas kernel."""
    _, params = tiny
    fns = vit.vitb16_layers(params)
    jaxpr = jax.make_jaxpr(lambda y: cnn.apply_layers(fns, y))(_frames())
    n = str(jaxpr).count("pallas_call[")
    assert n == (2 if kernels == "pallas" else 0)


def test_init_has_the_reference_layout(chip, tiny):
    cfg, params = tiny
    want = jax.tree.map(lambda a: a.shape, params)
    got = vit.vitb16_init(jax.random.PRNGKey(0), 32, 48, d=32, heads=2,
                          mlp=64, blocks=2, num_classes=10)
    assert jax.tree.map(lambda a: a.shape, got) == want


def test_placed_rounds_match_the_reference(chip, tiny):
    """Admission, ``compile_plan`` and ``ExecutionEngine.run`` on nodes too
    small in compute for a whole frame: every frame splits and every answer
    is the reference's."""
    cfg, params = tiny
    tr = chip["runtime"].load_json(CHIP / "traffic" / "split_cams4.json")
    tr["node_comp_flops"] = 0.5e6
    prob = chip["driver"].deployment(cfg, tr)
    ids = list(range(tr["streams"]))
    plan = AdmissionController(tr["planner"]).admit(
        prob, SnapshotView(prob.rates), request_ids=ids)
    assert plan.n_admitted == len(ids)
    assert all(len(set(plan.assign[r].tolist())) >= 2 for r in ids)
    frames = _frames(len(ids), seed=1)
    report = ExecutionEngine(vit.vitb16_layers(params)).run(
        compile_plan(plan), frames)
    want = chip["ref"].forward(cfg, params, frames)
    for r in ids:
        assert _rel(report.outputs[r], want[r]) < 1e-5


def test_vitb16_profile_is_the_benchmark_count(chip):
    cfg = chip["runtime"].load_json(CHIP / "configs" / "vitb16.json")
    want = chip["costs"].program_profile(cfg)
    got = vitb16_profile()
    assert got.num_layers == want.num_layers == 14
    for a, b in zip(got.layers, want.layers):
        assert (a.memory_bytes, a.compute_flops, a.output_bytes,
                a.params_bytes) == (b.memory_bytes, b.compute_flops,
                                    b.output_bytes, b.params_bytes), a.name
    assert got.input_bytes == want.input_bytes
    assert got.total_flops == pytest.approx(146.99e9, abs=5e6)
    assert sum(ly.params_bytes for ly in got.layers) == 4 * 86_985_448
    assert got.layers[0].output_bytes == 741 * 768 * 4


def test_vitb16_is_registered_on_the_served_path(tiny):
    _, params = tiny
    assert NODE_MEM_BYTES["vitb16"] == 512e6
    small = vitb16_profile(32, 48, d=32, mlp=64, blocks=2, num_classes=10)
    fns = layer_fns_for(small, params)
    x = jnp.asarray(_frames(1))
    np.testing.assert_array_equal(
        np.asarray(cnn.apply_layers(fns, x)),
        np.asarray(cnn.apply_layers(vit.vitb16_layers(params), x)))
