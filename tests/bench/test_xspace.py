"""Device time per model layer (``bench/xspace.py``): the protobuf wire
reader, the reduction on hand-worked data, the engine's ``engine.*``
spans on the profiler's clock, and a small trace recorded on a TPU v5e
(``tests/bench/data/layers.xplane.pb.gz``, ``record_layers_trace.py``)."""
from __future__ import annotations

import glob
import gzip
import importlib.util
from pathlib import Path

import numpy as np
import pytest

import bench_tiny  # noqa: F401  (puts the checkout on sys.path)
from bench import tracefile, xspace

DATA = Path(__file__).resolve().parent / "data"
OLD = DATA / "tiny.xplane.pb.gz"
NEW = DATA / "layers.xplane.pb.gz"
TPU = "/device:TPU:0"


def test_reader_and_program_share_one_tuple_of_scope_names():
    from repro.models import scopes
    assert xspace.SCOPES is scopes.SCOPES
    assert xspace.UNSCOPED not in scopes.SCOPES


@pytest.mark.parametrize("path,scope", [
    ("jit(_multi)/while/body/closed_call/kv_read/gather:", "kv_read"),
    ("jit(_multi)/mlp/closed_call/attn_core/dot_general:", "attn_core"),
    ("jit(_multi)/while/body/kv_write/jit(_mx_quantize)/gather:",
     "kv_write"),
    ("jit(_multi)/while/body/dynamic_update_slice:", None),
    ("jit(mlp)/add:", None),
    ("", None),
])
def test_an_op_takes_the_innermost_scope_of_its_path(path, scope):
    assert xspace.scope_of(path) == scope


def _meta(name, scope, text=None, program=1):
    tf_op = f"jit(_multi)/while/body/{scope}/x:" if scope else ""
    return xspace.OpMeta(name, text or f"%{name} = f32[] op()", tf_op,
                         program, scope)


def _hand_worked():
    meta = {1: _meta("fusion.7", "kv_read"),
            2: _meta("fusion.9", "mlp"),
            3: _meta("copy.2", None,
                     "%copy.2 = bf16[4]{0} copy(bf16[4]{0} %fusion.7)"),
            4: _meta("while.1", None)}
    ops = [xspace.Op(0, 60, 4),          # a loop: holds 1 and 2
           xspace.Op(0, 30, 1), xspace.Op(30, 55, 2),
           xspace.Op(70, 90, 3),
           xspace.Op(210, 250, 1),
           xspace.Op(400, 420, 2)]       # in a window past t1
    win = lambda a, b, n: xspace.Span(a, b, xspace.WINDOW,
                                      {"steps": n, "live": 2})
    spans = [xspace.Span(0, 300, "engine.step", {}), win(0, 100, 4),
             xspace.Span(100, 200, "engine.fanout", {}), win(200, 300, 4),
             win(390, 450, 8)]
    return xspace.Layers({TPU: ops}, {TPU: meta}, spans)


def test_decode_table_on_hand_worked_ops():
    layers = _hand_worked()
    tab = xspace.decode_table(layers, 0, 350)
    assert tab["steps"] == 8 and tab["windows"] == 2
    assert tab["ns"] == {"kv_read": 70, "mlp": 25, xspace.UNSCOPED: 20}
    assert tab["busy_ns"] == 60 + 20 + 40
    assert xspace.ms_per_step(tab, ["kv_read"]) == 70 / 1e6 / 8
    assert xspace.ms_per_step(tab, ["kv_read", "mlp"]) == 95 / 1e6 / 8
    assert xspace.ms_per_step(None, ["kv_read"]) is None
    assert xspace.decode_table(layers, 0, 50) is None    # no whole window


def test_top_unscoped_names_the_scope_of_the_operand():
    assert xspace.top_unscoped(_hand_worked(), 0, 350) == \
        [["copy", "kv_read", "", 20e-9]]


def test_busy_inside_engine_spans():
    layers = _hand_worked()
    busy, inside = xspace.busy_in_spans(layers, 0, 500)
    assert busy == 60 + 20 + 40 + 20
    assert inside == busy                # 400-420 lies in a window span
    layers.spans = layers.spans[:2]      # engine.step and one window
    assert xspace.busy_in_spans(layers, 0, 500) == (busy, busy - 20)
    assert xspace.steps_range(layers, 0, 500) == (0, 300)
    assert xspace.steps_range(layers, 10, 500) is None


@pytest.fixture(scope="module")
def old():
    """The trace of a program without scopes or engine spans."""
    return xspace.load(str(OLD))


def test_a_trace_without_scopes_or_engine_spans_reads_nothing(old):
    layers = old
    assert layers.spans == []
    assert xspace.decode_table(layers, 0, 1 << 62) is None
    assert all(m.scope is None for m in layers.meta[TPU].values())


def test_wire_reader_reads_the_ops_profiledata_reads(old):
    layers = old
    ops = tracefile.load(str(OLD)).devices[TPU]
    mine = layers.ops[TPU]
    meta = layers.meta[TPU]
    assert len(mine) == len(ops) > 100
    assert [(o.start, o.end, meta[o.meta].name) for o in mine] == \
        [(o.start, o.end, o.name) for o in ops]


@pytest.fixture(scope="module")
def chip():
    """The recorded trace as the reader and as ProfileData see it."""
    return xspace.load(str(NEW)), tracefile.load(str(NEW))


def _window(trace):
    (t0, t1), = tracefile.span_bounds(trace.spans, "bench.window")
    return t0, t1


def test_chip_trace_layers_add_up_to_busy_leaf_time(chip):
    layers, trace = chip
    t0, t1 = _window(trace)
    tab = xspace.decode_table(layers, t0, t1)
    assert tab["windows"] >= 3 and tab["steps"] >= tab["windows"]
    assert set(tab["ns"]) <= set(xspace.SCOPES) | {xspace.UNSCOPED}
    assert {"kv_write", "kv_read", "attn_qkv", "attn_core", "attn_out",
            "mlp", "head", "sample", "embed"} <= set(tab["ns"])
    total = sum(tab["ns"].values())
    assert abs(total - tab["busy_ns"]) <= 0.01 * tab["busy_ns"]
    # the reduction sees the same device time ProfileData does
    wins = xspace.windows(layers, t0, t1)
    ops = trace.devices[TPU]
    assert tab["busy_ns"] == sum(tracefile.busy_ns(ops, w.start, w.end)
                                 for w in wins)


def test_chip_trace_has_engine_phases_with_their_stats(chip):
    layers, trace = chip
    t0, t1 = _window(trace)
    names = {s.name for s in layers.spans}
    assert {"engine.step", "engine.admit", "engine.prefill",
            xspace.WINDOW, "engine.fanout"} <= names
    for s in layers.spans:
        if s.name == xspace.WINDOW:
            assert s.stats["steps"] >= 1 and s.stats["live"] >= 1
        if s.name == "engine.prefill":
            assert s.stats["group"] >= 1 and s.stats["bucket"] >= 1
    # between the first and the last whole step, the device works inside
    # the engine's host phases
    a, b = xspace.steps_range(layers, t0, t1)
    assert t0 <= a < b <= t1
    busy, inside = xspace.busy_in_spans(layers, a, b)
    assert busy > 0 and inside >= 0.95 * busy


def _xplane_pb2():
    pytest.importorskip("google.protobuf")
    spec = importlib.util.find_spec("tensorflow")
    if spec is None or not spec.submodule_search_locations:
        pytest.skip("no xplane_pb2 to compare with")
    path = Path(list(spec.submodule_search_locations)[0]) \
        / "tsl" / "profiler" / "protobuf" / "xplane_pb2.py"
    if not path.exists():
        pytest.skip("no xplane_pb2 to compare with")
    s = importlib.util.spec_from_file_location("_xplane_pb2", path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


def test_wire_reader_matches_xplane_pb2(chip):
    pb2 = _xplane_pb2()
    layers, _ = chip
    space = pb2.XSpace()
    with gzip.open(NEW, "rb") as f:
        space.ParseFromString(f.read())

    def value(st, names):
        kind = st.WhichOneof("value")
        v = getattr(st, kind)
        return names[v] if kind == "ref_value" else v

    spans = []
    for plane in space.planes:
        names = {k: m.name for k, m in plane.stat_metadata.items()}
        if plane.name == TPU:
            for k, m in plane.event_metadata.items():
                st = {names[s.metadata_id]: value(s, names)
                      for s in m.stats}
                assert layers.meta[TPU][k].tf_op == st.get("tf_op", "")
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    name = plane.event_metadata[ev.metadata_id].name
                    if name.startswith(xspace.SPAN_PREFIX):
                        s = (line.timestamp_ns * 1000 + ev.offset_ps)
                        spans.append((
                            s // 1000, s // 1000 + ev.duration_ps // 1000,
                            name, {names[x.metadata_id]: value(x, names)
                                   for x in ev.stats}))
    assert sorted(spans, key=lambda s: s[:3]) == sorted(
        ((s.start, s.end, s.name, s.stats) for s in layers.spans),
        key=lambda s: s[:3])


def test_engine_phases_reach_the_profiler_clock(tmp_path):
    """A tiny engine served under the profiler (on the CPU here): one
    ``engine.step`` per step, each decode window with its steps and live
    slots, each prefill bucket with its group and length."""
    import jax
    from repro.models import Model, load_reduced
    from repro.models.config import QuantPolicy
    from repro.serve import ContinuousBatchingEngine
    cfg = load_reduced("chatglm3_6b",
                       mx=QuantPolicy.parse("kv=int8@32:ocp"))
    model = Model(cfg)
    eng = ContinuousBatchingEngine(model, model.init(jax.random.PRNGKey(0)),
                                   max_slots=2, page_size=8, max_len=48,
                                   sync_every=4)
    rng = np.random.default_rng(0)
    for n in (5, 9, 12):
        eng.add_request(rng.integers(0, cfg.vocab, n), 6)
    jax.profiler.start_trace(str(tmp_path))
    n_steps = 0
    while eng.scheduler.has_work():
        eng.step()
        n_steps += 1
    jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = xspace.load(path).spans
    by = lambda n: [s for s in spans if s.name == n]
    assert len(by("engine.step")) == n_steps
    wins = by(xspace.WINDOW)
    assert sum(s.stats["steps"] for s in wins) == eng.n_steps
    assert [s.stats["live"] for s in wins][0] == 2
    assert sorted((s.stats["group"], s.stats["bucket"])
                  for s in by("engine.prefill")) == [(1, 8), (1, 16),
                                                     (1, 16)]
    assert len(by("engine.fanout")) == len(wins)
