"""Reduce a profiler trace (``.xplane.pb``) to device busy time, the
device ops that took most time, and idle gaps labelled by the
benchmark's host spans.

Read with ``jax.profiler.ProfileData`` alone.  Device planes are named
``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per executed
operation.  Host spans are the ``bench.*`` ``TraceAnnotation`` events the
harness opens around its calls into each layer; they share the trace's
clock with the device events.
"""
from __future__ import annotations

import dataclasses
import glob
import re
from pathlib import Path
from typing import Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Op:
    start: int          # ns, on the trace's clock
    end: int
    name: str           # the HLO instruction's own name ("fusion.361")


@dataclasses.dataclass
class Span:
    start: int
    end: int
    name: str


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Op]]        # device plane name -> ops, sorted
    spans: List[Span]                   # bench.* host spans


def find_xplane(log_dir: str) -> str:
    hits = sorted(glob.glob(str(Path(log_dir) / "**" / "*.xplane.pb*"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return hits[-1]


def op_name(text: str) -> str:
    """An op event's own name: TPU traces name an op by its whole HLO
    instruction ("%fusion.361 = u8[...] fusion(%operand, ...)"), whose
    operands name other ops; only the part before " = " is its own."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` (or its gzip, ``.xplane.pb.gz``)."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        import gzip
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    devices: Dict[str, List[Op]] = {}
    spans: List[Span] = []
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    s = int(ev.start_ns)
                    ops.append(Op(s, s + int(ev.duration_ns),
                                  op_name(ev.name)))
            devices[plane.name] = sorted(ops, key=lambda o: o.start)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = int(ev.start_ns)
                        spans.append(Span(s, s + int(ev.duration_ns),
                                          ev.name))
    return Trace(devices, sorted(spans, key=lambda s: s.start))


def merged(ops: List[Op], t0: int, t1: int) -> List[Tuple[int, int]]:
    """Union of the ops' intervals, clipped to [t0, t1]."""
    out: List[Tuple[int, int]] = []
    for o in ops:
        a, b = max(o.start, t0), min(o.end, t1)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def busy_ns(ops: List[Op], t0: int, t1: int) -> int:
    return sum(b - a for a, b in merged(ops, t0, t1))


def leaves(ops: List[Op]) -> List[Op]:
    """The ops that hold no other op: a TPU trace nests the ops of a
    loop's body inside the loop's own event, so summing every op counts
    the body twice."""
    ops = sorted(ops, key=lambda o: (o.start, -o.end))
    return [o for o, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or nxt.start >= o.end]


def top_ops(ops: List[Op], t0: int, t1: int, n: int = 10):
    """The ``n`` op names with the most device time in [t0, t1], counted
    over leaf ops ([name, seconds], most first)."""
    tot: Dict[str, int] = {}
    for o in leaves(ops):
        if t0 <= o.start < t1:
            key = re.sub(r"\.\d+$", "", o.name)
            tot[key] = tot.get(key, 0) + (min(o.end, t1) - o.start)
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in top]


def span_at(spans: List[Span], t: int) -> Optional[str]:
    """The innermost (shortest) host span open at ``t``."""
    best = None
    for s in spans:
        if s.start > t:
            break
        if s.end >= t and (best is None
                           or s.end - s.start < best.end - best.start):
            best = s
    return None if best is None else best.name


def idle_gaps(ops: List[Op], spans: List[Span], t0: int, t1: int,
              n: int = 10):
    """The ``n`` longest device-idle gaps in [t0, t1], each labelled by
    the host span open at its middle ([label, seconds], longest first)."""
    busy = merged(ops, t0, t1)
    edges = [t0] + [x for ab in busy for x in ab] + [t1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b in gaps[:n]:
        out.append([span_at(spans, (a + b) // 2) or "outside bench spans",
                    (b - a) / 1e9])
    return out


def span_bounds(spans: List[Span], name: str) -> List[Tuple[int, int]]:
    return [(s.start, s.end) for s in spans if s.name == name]
