"""Put a profiler trace's device time down to the model's layers.

``tracefile`` reads a trace through ``jax.profiler.ProfileData``, which
shows each device op by its HLO name alone ("fusion.361").  The layer an
op belongs to lives elsewhere in the ``.xplane.pb``: in the op's *event
metadata*, whose ``tf_op`` stat is the op_name path the program's
``jax.named_scope``s wrote
(``jit(_multi)/while/body/closed_call/kv_read/gather:``).  The engine's
host phases (``engine.step``, ``engine.decode_window`` with its ``steps``
and ``live``, ...) are ``TraceAnnotation`` events whose stats sit on the
event.  This module decodes the ``XSpace`` protobuf wire format itself,
so it needs nothing beyond the standard library.

An op belongs to the innermost model scope (``SCOPES``) in its ``tf_op``
path; a fusion carries its root's path, so it takes its root's scope.  An
op with no scope in its path (XLA's copies and relayouts carry no
``tf_op`` at all; loop bookkeeping sits outside every layer) is
"unscoped".  Device time counts leaf ops only, as in ``tracefile``.
"""
from __future__ import annotations

import dataclasses
import gzip
import re
import struct
from typing import Dict, List, Optional, Tuple

from bench.tracefile import OPS_LINE, leaves, merged

try:
    from repro.models.scopes import SCOPES
except ImportError:             # a program without model scopes
    SCOPES: Tuple[str, ...] = ()

UNSCOPED = "unscoped"
SPAN_PREFIX = "engine."
WINDOW = "engine.decode_window"
STEP = "engine.step"


@dataclasses.dataclass
class Op:
    start: int                  # ns, on the trace's clock
    end: int
    meta: int                   # event metadata id in its plane


@dataclasses.dataclass
class OpMeta:
    name: str                   # HLO instruction name ("fusion.361")
    text: str                   # the whole instruction
    tf_op: str
    program: int                # program_id: names are unique within one
    scope: Optional[str]


@dataclasses.dataclass
class Span:
    start: int
    end: int
    name: str
    stats: Dict[str, object]


@dataclasses.dataclass
class Layers:
    ops: Dict[str, List[Op]]            # device plane -> ops, by start
    meta: Dict[str, Dict[int, OpMeta]]  # device plane -> metadata by id
    spans: List[Span]                   # engine.* host spans, by start


# ---------------------------------------------------------- wire format
def _varint(b: bytes, i: int) -> Tuple[int, int]:
    x = b[i]
    i += 1
    if x < 0x80:
        return x, i
    x &= 0x7F
    shift = 7
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(b: bytes, i: int, end: int):
    """(field number, wire type, value) of one message in b[i:end]; a
    length-delimited value is its (start, end) in ``b``."""
    while i < end:
        key, i = _varint(b, i)
        wt = key & 7
        if wt == 0:
            v, i = _varint(b, i)
        elif wt == 2:
            n, i = _varint(b, i)
            v = (i, i + n)
            i += n
        elif wt == 1:
            v = b[i:i + 8]
            i += 8
        elif wt == 5:
            v = b[i:i + 4]
            i += 4
        else:
            raise ValueError(f"wire type {wt} at byte {i}")
        yield key >> 3, wt, v


def _int64(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _str(b: bytes, r: Tuple[int, int]) -> str:
    return b[r[0]:r[1]].decode("utf-8", "replace")


def _stat(b: bytes, r, stat_names: Dict[int, str]) -> Tuple[str, object]:
    """XStat -> (name, value); a ref value names another stat metadata."""
    key, val = 0, None
    for f, _, v in _fields(b, *r):
        if f == 1:
            key = v
        elif f == 2:
            val = struct.unpack("<d", v)[0]
        elif f == 3:
            val = v
        elif f == 4:
            val = _int64(v)
        elif f in (5, 6):
            val = _str(b, v)
        elif f == 7:
            val = stat_names.get(v, "")
    return stat_names.get(key, str(key)), val


def _map_entries(b: bytes, ranges):
    """The (key, value range) of each entry of a protobuf map field."""
    for r in ranges:
        k, v = 0, None
        for f, _, x in _fields(b, *r):
            if f == 1:
                k = x
            elif f == 2:
                v = x
        yield k, v


# ------------------------------------------------------------- reading
def scope_of(tf_op: str) -> Optional[str]:
    """The innermost model scope in an op_name path, or None."""
    for part in reversed(tf_op.split("/")):
        name = part.split(":", 1)[0]
        if name in SCOPES:
            return name
    return None


def _op_meta(b: bytes, r, stat_names) -> OpMeta:
    text, stats = "", {}
    for f, _, v in _fields(b, *r):
        if f == 2:
            text = _str(b, v)
        elif f == 5:
            k, val = _stat(b, v, stat_names)
            stats[k] = val
    tf_op = str(stats.get("tf_op") or "")
    return OpMeta(text.split(" = ", 1)[0].strip().lstrip("%"), text,
                  tf_op, int(stats.get("program_id") or 0), scope_of(tf_op))


def _plane(b: bytes, r, layers: Layers) -> None:
    name, lines, metas, stat_md = "", [], [], []
    for f, _, v in _fields(b, *r):
        if f == 2:
            name = _str(b, v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            metas.append(v)
        elif f == 5:
            stat_md.append(v)
    device = re.fullmatch(r"/device:TPU:\d+", name) is not None
    if not device and not name.startswith("/host:"):
        return
    stat_names = {}
    for k, v in _map_entries(b, stat_md):
        for f, _, x in _fields(b, *v):
            if f == 2:
                stat_names[k] = _str(b, x)
    events = []                 # (metadata id, start ps, dur ps, stats)
    for lr in lines:
        lname, ts, evs = "", 0, []
        for f, _, v in _fields(b, *lr):
            if f == 2:
                lname = _str(b, v)
            elif f == 3:
                ts = _int64(v)
            elif f == 4:
                evs.append(v)
        if device and lname != OPS_LINE:
            continue
        for er in evs:
            mid = off = dur = 0
            st = []
            for f, _, v in _fields(b, *er):
                if f == 1:
                    mid = v
                elif f == 2:
                    off = _int64(v)
                elif f == 3:
                    dur = _int64(v)
                elif f == 4 and not device:
                    st.append(v)
            events.append((mid, ts * 1000 + off, dur, st))
    if device:
        layers.meta[name] = {k: _op_meta(b, v, stat_names)
                             for k, v in _map_entries(b, metas)}
        layers.ops[name] = sorted(
            (Op(s // 1000, s // 1000 + d // 1000, m)
             for m, s, d, _ in events),
            key=lambda o: o.start)
        return
    names = {}
    for k, v in _map_entries(b, metas):
        for f, _, x in _fields(b, *v):
            if f == 2:
                names[k] = _str(b, x)
    for mid, s, d, st in events:
        n = names.get(mid, "")
        if n.startswith(SPAN_PREFIX):
            layers.spans.append(Span(
                s // 1000, s // 1000 + d // 1000, n,
                dict(_stat(b, x, stat_names) for x in st)))


def parse(raw: bytes) -> Layers:
    """Device ops with their metadata and the ``engine.*`` host spans of
    one serialized ``XSpace``."""
    layers = Layers({}, {}, [])
    for f, wt, v in _fields(raw, 0, len(raw)):
        if f == 1 and wt == 2:
            _plane(raw, v, layers)
    layers.spans.sort(key=lambda s: s.start)
    return layers


def load(path: str) -> Layers:
    """Read an ``.xplane.pb`` (or its gzip, ``.xplane.pb.gz``)."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        return parse(f.read())


# ----------------------------------------------------------- reduction
def windows(layers: Layers, t0: int, t1: int) -> List[Span]:
    """The ``engine.decode_window`` spans wholly inside [t0, t1]."""
    return [s for s in layers.spans
            if s.name == WINDOW and s.start >= t0 and s.end <= t1]


def _by_window(ops: List[Op], wins: List[Span]) -> List[List[Op]]:
    """The ops that start inside each window (both sorted by start)."""
    out: List[List[Op]] = [[] for _ in wins]
    j = 0
    for o in ops:
        while j < len(wins) and wins[j].end <= o.start:
            j += 1
        if j == len(wins):
            break
        if wins[j].start <= o.start:
            out[j].append(o)
    return out


def device_plane(layers: Layers) -> Optional[str]:
    return sorted(layers.ops)[0] if layers.ops else None


def decode_table(layers: Layers, t0: int, t1: int,
                 plane: Optional[str] = None) -> Optional[Dict]:
    """Device time of the leaf ops inside the decode windows wholly inside
    [t0, t1], per model scope (``UNSCOPED`` for the rest), beside the
    windows' decode steps and the union of those ops' intervals.  None
    when the trace holds no such window or no device."""
    plane = plane or device_plane(layers)
    wins = windows(layers, t0, t1)
    if plane is None or not wins:
        return None
    steps = sum(int(w.stats.get("steps") or 0) for w in wins)
    meta = layers.meta[plane]
    ns: Dict[str, int] = {}
    for win in _by_window(leaves(layers.ops[plane]), wins):
        for o in win:
            m = meta.get(o.meta)
            key = (m.scope if m is not None else None) or UNSCOPED
            ns[key] = ns.get(key, 0) + (o.end - o.start)
    busy = sum(b - a for w, win in zip(wins, _by_window(layers.ops[plane],
                                                        wins))
               for a, b in merged(win, w.start, w.end))
    return {"steps": steps, "windows": len(wins), "ns": ns,
            "busy_ns": busy}


def ms_per_step(table: Optional[Dict], scopes) -> Optional[float]:
    """Milliseconds per decode step of ``scopes`` in a ``decode_table``."""
    if not table or not table["steps"]:
        return None
    return sum(table["ns"].get(s, 0) for s in scopes) / 1e6 / table["steps"]


def top_unscoped(layers: Layers, t0: int, t1: int, n: int = 10,
                 plane: Optional[str] = None):
    """The ``n`` unscoped ops with the most device time inside the decode
    windows, by HLO name, each with the first model scope among its
    operands and its own op_name path ([name, operand scope, path,
    seconds], most first)."""
    plane = plane or device_plane(layers)
    wins = windows(layers, t0, t1)
    if plane is None or not wins:
        return []
    meta = layers.meta[plane]
    by_name = {(m.program, m.name): m for m in meta.values()}
    tot: Dict[Tuple[str, str, str], int] = {}
    for win in _by_window(leaves(layers.ops[plane]), wins):
        for o in win:
            m = meta.get(o.meta)
            if m is not None and m.scope is not None:
                continue
            key = ("?", UNSCOPED, "")
            if m is not None:
                args = m.text.split(" = ", 1)[-1]
                srcs = (by_name.get((m.program, a))
                        for a in re.findall(r"%([\w.\-]+)", args))
                scope = next((x.scope for x in srcs
                              if x is not None and x.scope), UNSCOPED)
                key = (re.sub(r"\.\d+$", "", m.name), scope,
                       m.tf_op.rstrip(":"))
            tot[key] = tot.get(key, 0) + (o.end - o.start)
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[*k, v / 1e9] for k, v in top]


def steps_range(layers: Layers, t0: int, t1: int
                ) -> Optional[Tuple[int, int]]:
    """From the start of the first ``engine.step`` wholly inside [t0, t1]
    to the end of the last.  The steps in flight when the profiler
    starts or stops have no span (an annotation is recorded only when
    the profiler runs at both its ends), so their device work lies
    outside every ``engine.*`` span by construction."""
    steps = [s for s in layers.spans if s.name == STEP
             and s.start >= t0 and s.end <= t1]
    if not steps:
        return None
    return steps[0].start, max(s.end for s in steps)


def busy_in_spans(layers: Layers, t0: int, t1: int,
                  plane: Optional[str] = None) -> Tuple[int, int]:
    """(device busy ns in [t0, t1], the part of it inside any
    ``engine.*`` host span)."""
    plane = plane or device_plane(layers)
    if plane is None:
        return 0, 0
    busy = merged(layers.ops[plane], t0, t1)
    cover: List[Tuple[int, int]] = []
    for s in layers.spans:              # sorted by start
        a, b = max(s.start, t0), min(s.end, t1)
        if b <= a:
            continue
        if cover and a <= cover[-1][1]:
            cover[-1] = (cover[-1][0], max(cover[-1][1], b))
        else:
            cover.append((a, b))
    inside, j = 0, 0
    for a, b in busy:
        while j < len(cover) and cover[j][1] <= a:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < b:
            inside += min(b, cover[k][1]) - max(a, cover[k][0])
            k += 1
    return sum(b - a for a, b in busy), inside
