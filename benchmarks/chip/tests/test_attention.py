"""``attention_roofline`` divides by ``primitive_s["pallas_call"]``, the
device time of every Pallas op of the window.  That is the attention
kernel's own time only while the ViT stage closures hold no other Pallas
kernel: here every closure of the cell's plan, at its widths and batch,
holds exactly one ``pallas_call`` per encoder block it runs."""

import jax
import jax.numpy as jnp
import pytest

from harness import runtime

CELL_CONFIG = runtime.CHIP_DIR / "configs" / "vitb16.json"


@pytest.fixture
def pallas(monkeypatch):
    from repro.kernels import ops
    monkeypatch.setenv("REPRO_KERNELS", "pallas")
    ops._mode.cache_clear()
    yield
    ops._mode.cache_clear()


def _pallas_calls(jaxpr) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "pallas_call"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _pallas_calls(sub)
    return n


def test_one_pallas_call_per_block_in_every_closure(pallas):
    from repro.core import SnapshotView
    from repro.exec import compile_plan
    from repro.models import cnn, vit
    from repro.runtime.serve import AdmissionController
    driver = runtime.load_module(runtime.CHIP_DIR / "drivers"
                                 / "camera_rounds.py")
    ref = runtime.reference("vit")
    cfg = runtime.load_json(CELL_CONFIG)
    tr = runtime.load_json(runtime.CHIP_DIR / "traffic" / "split_cams4.json")
    prob = driver.deployment(cfg, tr)
    plan = AdmissionController(tr["planner"]).admit(
        prob, SnapshotView(prob.rates), request_ids=list(range(tr["streams"])))
    closures = {(t.layer_start, t.layer_end, len(t.requests))
                for t in compile_plan(plan).tasks}
    assert len(closures) >= 2
    params = jax.eval_shape(lambda: ref.init(cfg, 1))
    blocks = {j for j, unit in enumerate(cfg["units"])
              if any(op["op"] == "attention" for op in unit)}
    for s, e, b in sorted(closures):
        shape = ((b, *cfg["frame"]) if s == 0
                 else (b, 741, cfg["units"][0][0]["d"]))
        jaxpr = jax.make_jaxpr(
            lambda p, x: cnn.apply_layers(vit.vitb16_layers(p), x, s, e))(
            params, jax.ShapeDtypeStruct(shape, jnp.float32))
        assert _pallas_calls(jaxpr.jaxpr) == len(blocks & set(range(s, e)))
