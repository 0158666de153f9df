"""Dry-run path tests on a small (8-device) mesh in a subprocess, plus unit
tests for the HLO collective parser."""
import os
import subprocess
import sys
import textwrap

import pytest

from repro.launch.hlo_stats import collective_bytes, _shape_bytes

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_shape_bytes_parser():
    assert _shape_bytes("bf16[4,512]{1,0}") == 4 * 512 * 2
    assert _shape_bytes("f32[2,3]") == 24
    assert _shape_bytes("(f32[2,2]{1,0}, u8[16]{0})") == 16 + 16
    assert _shape_bytes("pred[8]") == 8


def test_collective_bytes_parser():
    hlo = """
  %ag = bf16[8,128]{1,0} all-gather(%x), dimensions={0}
  %ar.1 = f32[64]{0} all-reduce-start(%y), to_apply=%add
  %ar.2 = f32[64]{0} all-reduce-done(%ar.1)
  %a2a = u8[32,4]{1,0} all-to-all(%z), dimensions={1}
"""
    out = collective_bytes(hlo)
    assert out["all-gather"] == 8 * 128 * 2
    assert out["all-reduce"] == 64 * 4      # start counted, done skipped
    assert out["all-to-all"] == 32 * 4
    assert out["count"] == 3


@pytest.mark.slow
def test_dryrun_small_mesh_lower_compile():
    """The real cell-building path (reduced arch, 8 host devices) lowers,
    compiles, and yields cost/memory analyses for all three cell kinds."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count=8")
        import jax
        from repro.launch.cells import build_cell, lower_cell
        import repro.launch.cells as C
        import repro.models.registry as R
        import dataclasses

        # shrink: monkeypatch the config loader to the reduced config with
        # dims divisible by the 2x4 test mesh
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        real_load = R.load_config
        def tiny(arch, **over):
            cfg = R.load_reduced(arch, dtype="bfloat16",
                                 param_dtype="bfloat16")
            return dataclasses.replace(cfg, **over) if over else cfg
        C.load_config = tiny
        import repro.launch.dryrun  # not imported: avoid 512-dev flag

        from repro.models.config import SHAPES, ShapeSpec
        SHAPES["train_4k"] = ShapeSpec("train_4k", 64, 8, "train")
        SHAPES["prefill_32k"] = ShapeSpec("prefill_32k", 64, 8, "prefill")
        SHAPES["decode_32k"] = ShapeSpec("decode_32k", 64, 8, "decode")

        for shape in ("train_4k", "prefill_32k", "decode_32k"):
            cell = build_cell("chatglm3_6b", shape, mesh, "baseline")
            lowered = lower_cell(cell)
            compiled = lowered.compile()
            ca = compiled.cost_analysis()
            ma = compiled.memory_analysis()
            assert ca.get("flops", 0) > 0, shape
            assert ma.temp_size_in_bytes >= 0, shape
            print("OK", shape, ca.get("flops"))
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, env=env, timeout=560)
    assert out.returncode == 0, f"STDOUT:{out.stdout}\nSTDERR:{out.stderr}"
    assert out.stdout.count("OK") == 3
