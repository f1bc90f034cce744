"""The operation and byte counts of the ViT-B/16 configuration
(``configs/vitb16.json``), against the paper's and the program's."""

import pytest

from harness import costs
from test_costs import V5E, config


def test_vitb16_counts():
    """ViT-B/16 at the 326x595 frame: 740 patches and the class token, the
    paper's parameter count with the position table at 741 rows, one
    (741, 768) float32 sequence a hop."""
    cfg = config("vitb16")
    units = costs.units(cfg)
    assert len(units) == 14
    assert costs.frame_flops(cfg) / 1e9 == pytest.approx(146.99, abs=5e-3)
    assert costs.frame_flops(cfg) == 146_988_957_696.0
    assert sum(o.param_bytes for u in units for o in u.ops) == 4 * 86_985_448
    assert {u.output_bytes for u in units[:-1]} == {2_276_352}
    assert units[-1].output_bytes == 4000
    # a block: 12.18 GFLOP, of which QKᵀ and PV 4·T²·d; m its weights,
    # input and output (32.9 MB)
    block = units[1]
    attn = [o for o in block.ops if o.kernel == "attention"]
    assert len(attn) == 1 and attn[0].flops == 4.0 * 741 ** 2 * 768
    assert attn[0].in_bytes == 3 * 741 * 768 * 4
    assert attn[0].out_bytes == 741 * 768 * 4
    assert block.compute_flops == 12_176_206_848.0
    assert block.memory_bytes == 7_087_872 * 4 + 2 * 2_276_352
    assert attn[0].flops / block.compute_flops == pytest.approx(0.1385,
                                                                abs=5e-4)
    # no other op names a kernel
    assert {o.kernel for u in units for o in u.ops} == {None, "attention"}


def test_vitb16_units_match_the_program_profile():
    from repro.core import vitb16_profile
    prof = vitb16_profile()
    units = costs.units(config("vitb16"))
    assert len(units) == prof.num_layers
    for u, ly in zip(units, prof.layers):
        assert (u.memory_bytes, u.compute_flops, u.output_bytes) == (
            ly.memory_bytes, ly.compute_flops, ly.output_bytes), ly.name
    assert costs.input_bytes(config("vitb16")) == prof.input_bytes


@pytest.mark.parametrize("s,e,b", [(1, 2, 1), (1, 7, 2), (0, 14, 4),
                                   (0, 1, 3), (13, 14, 1)])
def test_attention_bound_is_flop_bound_per_block(s, e, b):
    cfg = config("vitb16")
    flops_s, bytes_s = V5E
    per_block = 4.0 * 741 ** 2 * 768 / flops_s
    n = sum(1 for j in range(s, e) if 1 <= j <= 12)
    got = costs.bound_s(cfg, s, e, b, flops_s, bytes_s)
    assert got.get("attention", 0.0) == pytest.approx(b * n * per_block,
                                                      rel=1e-12)
    assert set(got) <= {"attention"}


def test_vit_reference_param_shapes_match_the_program():
    import jax
    from references import vit as ref
    from repro.models import vit
    want = jax.eval_shape(lambda k: vit.vitb16_init(k),
                          jax.random.PRNGKey(0))
    got = {g: {n: s for n, (s, _) in names.items()}
           for g, names in ref.param_shapes(config("vitb16")).items()}
    assert jax.tree.map(lambda a: a.shape, want) == got
