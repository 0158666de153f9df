"""A configuration names where its program runs and what it is compared
with: a ``mesh`` spreads the weights over the cell's chips as the program
places them, ``weights`` and ``reference`` name the modules that make its
weights and its reference, and without them a run is what it was."""
from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import bench_tiny
from bench_tiny import ROOT

import bench  # noqa: E402
from bench import cells, run, serving  # noqa: E402
from bench.weights import make_params  # noqa: E402

DATA = ROOT / "tests" / "bench" / "data"
SEED = 2 ** 31 + 11

# sha256 over every leaf (path, dtype, shape, bytes) of the tiny cell's
# weights for SEED, as the harness made them before it knew of meshes
TINY_WEIGHTS_SHA256 = \
    "f07e7ab6c5211e3e530d991c82d39e07ca004245eeb1b7ac8b55142a72a1936a"

MESHED_RUN = """
import json, sys
sys.path[:0] = {paths!r}
import bench_tiny
from bench import run
cell = bench_tiny.cell()
cell.config["mesh"] = {{"data": 1, "model": 4}}
cell.chips = 4
split = {{}}

def look(sess):
    layers = sess.engine.params["layers"]
    for blk, name in (("attn", "wq"), ("mlp", "w1")):
        w = layers[blk][name]
        split[name] = [len(w.sharding.device_set),
                       w.addressable_shards[0].data.shape[-1], w.shape[-1]]

res = run.run_cell(cell, {seed}, 1.5, False, allow_cpu=True, mutate=look)
print(json.dumps({{"result": res, "split": split}}))
"""


def _digest(params) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        a = np.asarray(leaf)
        for part in (jax.tree_util.keystr(path), str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    return h.hexdigest()


def test_meshed_run_splits_the_weights_and_is_correct():
    """The tiny glm-shaped cell over a (data 1, model 4) mesh of four host
    devices: wq and w1 are split over all four, the run is correct, and
    memory is read from every device."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    paths = [str(ROOT / "tests" / "bench"), str(ROOT), str(ROOT / "src")]
    p = subprocess.run(
        [sys.executable, "-c", MESHED_RUN.format(paths=paths, seed=SEED)],
        capture_output=True, text=True, env=env, timeout=240)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    for name, (devices, shard, whole) in out["split"].items():
        assert devices == 4 and shard * 4 == whole, (name, out["split"])
    res = out["result"]
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["device"]["count"] == 4
    assert len(res["device"]["memory_peak_bytes_per_device"]) == 4


def test_without_a_mesh_the_weights_are_the_same_bits():
    cfg = bench_tiny.config()
    where = serving.placement(cfg, jax.devices()[:1])
    assert where.mesh is None and where.rules is None
    assert _digest(make_params(SEED, cfg["arch"])) == TINY_WEIGHTS_SHA256
    _, params = serving.build(cfg, SEED, where)
    assert _digest(params) == TINY_WEIGHTS_SHA256


def test_named_weights_and_reference_modules_serve_and_compare(monkeypatch):
    """A configuration that names its own modules is served with the
    weights of the one and compared by the other (copies of the present
    modules, kept with the tests)."""
    monkeypatch.setattr(bench, "__path__", [*bench.__path__, str(DATA)])
    cfg = bench_tiny.config()
    cfg.update(weights="tiny_weights", reference="tiny_reference")
    weights = cells.bench_module(cfg, "weights")
    reference = cells.bench_module(cfg, "reference")
    assert weights.__name__ == "bench.tiny_weights"
    assert reference.__name__ == "bench.tiny_reference"
    calls = []

    def spy(mod, fn):
        real = getattr(mod, fn)

        def wrapped(*a, **k):
            calls.append(f"{mod.__name__}.{fn}")
            return real(*a, **k)
        monkeypatch.setattr(mod, fn, wrapped)

    spy(weights, "make_params")
    spy(reference, "served_gaps")
    cell = bench_tiny.cell()
    cell.config = cfg
    res = run.run_cell(cell, SEED, 1.5, False, allow_cpu=True)
    assert res["correct"] is True, res["checks"]
    assert calls == ["bench.tiny_weights.make_params",
                     "bench.tiny_reference.served_gaps"]


@pytest.mark.parametrize("mesh,chips", [({"data": 1, "model": 2}, 4),
                                        ({"data": 2, "model": 4}, 4),
                                        ({"data": 1, "model": 4}, 1)],
                         ids=["short", "long", "one_chip"])
def test_a_mesh_that_is_not_the_cells_chips_is_refused_at_load(
        tmp_path, mesh, chips):
    spec = cells.load_benchmark()
    cfg = dict(bench_tiny.config(), mesh=mesh)
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "traffic").mkdir()
    (tmp_path / "bench" / "configs" / "tiny.json").write_text(json.dumps(cfg))
    (tmp_path / "bench" / "traffic" / "tiny.json").write_text(
        json.dumps(bench_tiny.mix()))
    spec["configs"].append({"name": "tiny", "source": "test",
                            "file": "bench/configs/tiny.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny.mesh", "config": "tiny",
                              "traffic": "tiny", "chips": chips,
                              "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    with pytest.raises(ValueError, match="mesh"):
        cells.load_cell("tiny.mesh", root=tmp_path)
    cfg["mesh"] = {"data": 1, "model": chips}
    (tmp_path / "bench" / "configs" / "tiny.json").write_text(json.dumps(cfg))
    assert cells.load_cell("tiny.mesh", root=tmp_path).chips == chips
