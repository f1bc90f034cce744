"""The ViT-B/16 cell's driver end to end on the CPU at a small size: the
checks of ``test_cells.py`` and the traced run of ``test_program.py``,
collected again here on ``vitb16-split-cams4``.  The cell's configuration
is read as a ViT of two blocks of width 32 on 32x48 frames
(``tests/data/vit_tiny.json``), with nodes small enough in compute that
every frame splits."""

from pathlib import Path

import pytest

import control
import test_cells
import test_program
from harness import peaks, runtime
from test_cells import (  # noqa: F401  (collected again on this cell)
    test_a_sound_run_is_correct,
    test_a_transfer_broken_in_every_round_is_caught,
    test_an_answer_altered_after_its_launch_is_caught,
    test_an_answer_altered_where_it_is_produced_is_caught,
    test_benchmark_files_alone_give_no_result,
    test_no_tpu_no_result,
)
from test_program import test_a_traced_run_on_the_cpu  # noqa: F401

VIT_TINY = Path(__file__).resolve().parent / "data" / "vit_tiny.json"
CELL = "vitb16-split-cams4"


@pytest.fixture(autouse=True)
def small(monkeypatch):
    import jax
    real = runtime.load_json

    def load(path):
        path = Path(path)
        if path.parent.name == "configs":
            return real(VIT_TINY)
        out = real(path)
        if path.parent.name == "traffic":
            out["node_comp_flops"] = 0.5e6
        return out

    monkeypatch.setattr(runtime, "load_json", load)
    monkeypatch.setattr(runtime, "accelerator",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(peaks, "peaks_for",
                        lambda kind: peaks.PEAKS["TPU v5 lite"])
    monkeypatch.setattr(test_cells, "CELL", CELL)
    monkeypatch.setattr(test_program, "CELL", CELL)


def test_the_control_fails_launch_gap():
    got, limits = control.readings(CELL, test_cells.SEED)
    assert got["launch_gap"] > limits["launch_gap"], got
