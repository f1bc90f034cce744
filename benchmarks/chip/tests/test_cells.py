"""The cell's driver end to end on the CPU at a small size: a sound run is
correct, a run whose timed path is broken underneath is not, and the
lower-precision control fails the cell's limits.  The runner's look for a
TPU is stood in for by the CPU devices, and the cell's files are read at a
small size: LeNet on 32x48 frames (``tests/data/lenet.json``), with nodes
small enough that every frame splits."""

import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import control
import run
from harness import peaks, runtime

LENET = Path(__file__).resolve().parent / "data" / "lenet.json"
CELL = "vgg16-split-cams4"
SEED = 2**31 + 12345


@pytest.fixture(autouse=True)
def small(monkeypatch):
    import jax
    real = runtime.load_json

    def load(path):
        path = Path(path)
        if path.parent.name == "configs":
            cfg = real(LENET)
            cfg["frame"] = [32, 48, 3]
            return cfg
        out = real(path)
        if path.parent.name == "traffic":
            out["node_mem_bytes"] = 0.4e6
        return out

    monkeypatch.setattr(runtime, "load_json", load)
    monkeypatch.setattr(runtime, "accelerator",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(peaks, "peaks_for",
                        lambda kind: peaks.PEAKS["TPU v5 lite"])


def cell(seed=SEED):
    return run.run_cell(CELL, seed, 0.5, False)


def test_a_sound_run_is_correct():
    res = cell()
    assert res["correct"], res["checks"]
    assert res["checks"]["chain_breaks"]["value"] == 0
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert "setup_s" in res["metrics"] and len(res["metrics"]) >= 2
    assert cell()["metrics"].keys() == res["metrics"].keys()


@pytest.mark.parametrize("fault", ["altered", "half_batch"])
def test_an_answer_altered_where_it_is_produced_is_caught(monkeypatch,
                                                          fault):
    """A last launch that returns a slightly wrong row, or a batched launch
    that computes half of its rows and repeats them for the rest."""
    import jax.numpy as jnp

    from repro.exec import engine

    real = engine.ExecutionEngine.closure

    def altered(self, start, end):
        fn = real(self, start, end)
        if fault == "altered" and end == len(self.layer_fns):
            return lambda x: fn(x).at[0, 0].add(1e-2 * abs(fn(x)).max())
        if fault == "half_batch":
            def half(x):
                b = x.shape[0]
                y = fn(x[:(b + 1) // 2])
                return jnp.concatenate([y, y[:b // 2]])
            return half
        return fn

    monkeypatch.setattr(engine.ExecutionEngine, "closure", altered)
    res = cell()
    assert not res["correct"]
    assert res["checks"]["launch_gap"]["value"] > 5e-3


@pytest.mark.parametrize("fault", ["altered", "lost", "swapped"])
def test_an_answer_altered_after_its_launch_is_caught(monkeypatch, fault):
    from repro.exec import engine

    real = engine.ExecutionEngine.run

    def broken(self, graph, frames, **kw):
        rep = real(self, graph, frames, **kw)
        a, b = graph.requests[0], graph.requests[-1]
        if fault == "altered":
            rep.outputs[a] = rep.outputs[a] * np.float32(1.001)
        elif fault == "lost":
            del rep.outputs[b]
        else:
            rep.outputs[a], rep.outputs[b] = rep.outputs[b], rep.outputs[a]
        return rep

    monkeypatch.setattr(engine.ExecutionEngine, "run", broken)
    res = cell()
    assert not res["correct"]
    assert (res["checks"]["bad_answers"]["value"]
            + res["checks"]["chain_breaks"]["value"]) > 0


@pytest.mark.parametrize("fault", ["narrowed", "corrupted", "reordered",
                                   "dropped"])
def test_a_transfer_broken_in_every_round_is_caught(monkeypatch, fault):
    """The in-process transport hands the consuming stage a changed
    activation, the same way in every round, so the reference fed the
    launch's own input would reproduce it: the chain check does not."""
    import dataclasses

    import jax.numpy as jnp

    from repro.transport import inproc

    real = inproc.InProcTransport.ship

    def broken(self, src, dst, array):
        res = real(self, src, dst, array)
        a = res.array
        if fault == "narrowed":
            a = a.astype(jnp.bfloat16).astype(a.dtype)
        elif fault == "corrupted":
            a = a.at[(0,) * a.ndim].add(1.0)
        elif fault == "reordered":
            a = jnp.flip(a, axis=1)
        else:
            a = jnp.zeros_like(a)
        return dataclasses.replace(res, array=a)

    monkeypatch.setattr(inproc.InProcTransport, "ship", broken)
    res = cell()
    assert not res["correct"]
    assert res["checks"]["chain_breaks"]["value"] > 0


def test_the_control_fails():
    got, limits = control.readings(CELL, SEED)
    assert any(got[k] > lim for k, lim in limits.items()), got


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", CELL,
         "--seed", "1", "--seconds", "1"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    out = _run_py(runtime.ROOT)
    assert out.returncode != 0 and out.stdout.strip() == ""
    assert "needs a TPU" in out.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(runtime.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(runtime.CHIP_DIR, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run_py(tmp_path)
    assert out.returncode != 0 and out.stdout.strip() == ""
