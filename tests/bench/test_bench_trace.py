"""The trace reduction and the operation counts, on hand-worked data and
on a small trace recorded on a TPU v5e (``tests/bench/data``)."""
from __future__ import annotations

from pathlib import Path

import pytest

import bench_tiny  # noqa: F401  (puts the checkout on sys.path)
from bench import flops, tracefile

DATA = Path(__file__).resolve().parent / "data"


def _ops(*iv, name="fusion.1"):
    return [tracefile.Op(a, b, name) for a, b in iv]


def test_busy_merges_overlaps_and_clips():
    ops = _ops((0, 10), (5, 20), (30, 40), (45, 60))
    assert tracefile.merged(ops, 0, 100) == [(0, 20), (30, 40), (45, 60)]
    assert tracefile.busy_ns(ops, 0, 100) == 45
    assert tracefile.busy_ns(ops, 8, 50) == 12 + 10 + 5


def test_idle_gaps_are_labelled_by_the_innermost_span():
    ops = _ops((0, 10), (40, 50), (52, 100))
    spans = [tracefile.Span(0, 100, "bench.step"),
             tracefile.Span(15, 35, "bench.admit")]
    gaps = tracefile.idle_gaps(ops, spans, 0, 100)
    assert gaps[0] == ["bench.admit", 30e-9]
    assert gaps[1] == ["bench.step", 2e-9]
    assert tracefile.idle_gaps(ops, [], 0, 110)[1] == \
        ["outside bench spans", 10e-9]


def test_top_ops_group_numbered_names():
    ops = _ops((0, 10), name="fusion.1") + _ops((10, 30), name="fusion.7") \
        + _ops((30, 35), name="copy.2")
    assert tracefile.top_ops(ops, 0, 100) == [["fusion", 30e-9],
                                              ["copy", 5e-9]]


def test_top_ops_count_a_loop_body_once():
    # a while loop's event holds its body's ops; the loop adds no time
    ops = _ops((0, 40), name="while.3") + _ops((0, 10), name="fusion.1") \
        + _ops((12, 30), name="fusion.2") + _ops((50, 60), name="copy.1")
    assert [o.name for o in tracefile.leaves(ops)] == \
        ["fusion.1", "fusion.2", "copy.1"]
    assert tracefile.top_ops(ops, 0, 55) == [["fusion", 28e-9],
                                             ["copy", 5e-9]]


ARCH = {"n_layers": 2, "d_model": 8, "n_heads": 4, "n_kv_heads": 2,
        "head_dim": 4, "d_ff": 16, "vocab": 32}


def test_flops_on_hand_worked_shapes():
    # per layer: wq 8x16 + wk, wv 8x8 + wo 16x8 + 3 x 8x16 = 768 weights
    assert flops.layer_matmul_params(ARCH) == 768
    assert flops.matmul_params(ARCH) == 2 * 768 + 8 * 32
    # one token over 5 keys: 4 * layers * heads * head_dim * 5
    assert flops.attn_flops(ARCH, 5) == 4 * 2 * 4 * 4 * 5
    assert flops.decode_flops(ARCH, 5) == 2 * (1536 + 256) + 640
    # a prompt of 3: layers over 3 tokens, causal 1+2+3 keys, head once
    assert flops.prefill_flops(ARCH, 3) == 2 * 1536 * 3 + 128 * 6 + 2 * 256


def test_step_work_counts_live_slots_only():
    from bench.serving import StepRec
    s = StepRec(0.0, 1.0, groups=[[3]], decode_n=2,
                decode_ctx=[(5, 2), (9, 1)])
    want = flops.prefill_flops(ARCH, 3) + flops.decode_flops(ARCH, 6) \
        + flops.decode_flops(ARCH, 7) + flops.decode_flops(ARCH, 10)
    assert flops.step_model_flops(ARCH, s) == want


@pytest.fixture(scope="module")
def chip_trace():
    return tracefile.load(tracefile.find_xplane(str(DATA)))


def test_chip_trace_has_device_ops_and_bench_spans(chip_trace):
    assert list(chip_trace.devices) == ["/device:TPU:0"]
    ops = chip_trace.devices["/device:TPU:0"]
    assert len(ops) > 100
    assert all(" = " not in o.name and not o.name.startswith("%")
               for o in ops)
    assert all(o.end >= o.start for o in ops)
    names = {s.name for s in chip_trace.spans}
    assert {"bench.window", "bench.step", "bench.admit"} <= names


def test_chip_trace_reduces_inside_its_window(chip_trace):
    ops = chip_trace.devices["/device:TPU:0"]
    (t0, t1), = tracefile.span_bounds(chip_trace.spans, "bench.window")
    busy = tracefile.busy_ns(ops, t0, t1)
    assert 0 < busy < t1 - t0
    top = tracefile.top_ops(ops, t0, t1)
    assert 1 <= len(top) <= 10
    assert [v for _, v in top] == sorted((v for _, v in top), reverse=True)
    assert sum(v for _, v in top) <= busy / 1e9 + 1e-9
    gaps = tracefile.idle_gaps(ops, chip_trace.spans, t0, t1)
    assert gaps and all(g[0].startswith("bench.") for g in gaps)
    assert sum(g for _, g in gaps) <= (t1 - t0 - busy) / 1e9 + 1e-9
