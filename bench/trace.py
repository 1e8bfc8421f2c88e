"""Reduction of a profiler trace to intervals, shared by the metric readers.

A JAX profile (``*.xplane.pb``) holds one plane per TPU (``/device:TPU:n``)
whose ``XLA Ops`` line has one event per HLO instruction executed, named
by the instruction's full HLO text (``%name = type opcode(operands),
attrs``), and a host plane whose lines hold host spans, the benchmark's
own ``TraceAnnotation`` among them. Host and device events share one
clock (nanoseconds from the start of the trace).

Only what the readers need is kept: per device the ``XLA Ops`` events,
on the host every span, and the measured window (the host span named
:data:`WINDOW_SPAN`). :func:`to_json` / :func:`from_json` store that much
of a trace, which is how the tests' recorded trace is kept.
"""
from __future__ import annotations

import dataclasses
import json
import re
from pathlib import Path

WINDOW_SPAN = "bench.window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
# Instructions that only hold others: their events span the ops they
# run, with the gaps between those, so they never count as work.
CONTAINERS = frozenset({"while", "conditional", "call"})


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float  # ns
    end: float  # ns

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    devices: dict[str, list[Event]]
    host: list[Event]
    window: tuple[float, float]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def load(path: str | Path) -> Trace:
    """Read an ``.xplane.pb``; raises if it holds no measured window."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    devices: dict[str, list[Event]] = {}
    host: list[Event] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            evs = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs.extend(
                        Event(e.name, e.start_ns, e.end_ns)
                        for e in line.events
                    )
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(
                    Event(e.name, e.start_ns, e.end_ns) for e in line.events
                )
    spans = [e for e in host if e.name == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"no {WINDOW_SPAN!r} span in {path}")
    w = spans[0]
    return Trace(
        {k: sorted(v, key=lambda e: e.start) for k, v in devices.items()},
        host,
        (w.start, w.end),
    )


def to_json(trace: Trace) -> str:
    return json.dumps({
        "window": list(trace.window),
        "devices": {
            k: [[e.name, e.start, e.end] for e in v]
            for k, v in trace.devices.items()
        },
        "host": [[e.name, e.start, e.end] for e in trace.host],
    })


def from_json(text: str) -> Trace:
    d = json.loads(text)
    return Trace(
        {k: [Event(*e) for e in v] for k, v in d["devices"].items()},
        [Event(*e) for e in d["host"]],
        tuple(d["window"]),
    )


# -- HLO op text -------------------------------------------------------------

_TYPE = re.compile(r"([a-z]+[0-9]*)\[([0-9,]*)\]")
_ITEMSIZE = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "s32": 4, "u32": 4,
    "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
}


def _skip_type(text: str, i: int) -> int:
    """Index just past the HLO type (a tuple in parentheses or one
    array type) that starts at ``text[i]``."""
    if text[i] == "(":
        depth = 0
        for j in range(i, len(text)):
            depth += {"(": 1, ")": -1}.get(text[j], 0)
            if depth == 0:
                return j + 1
        return len(text)
    j = text.find(" ", i)
    return len(text) if j < 0 else j


def opcode(name: str) -> str:
    """The HLO opcode of an ``XLA Ops`` event (``fusion``, ``while``,
    ``custom-call``, ``collective-permute-done``...); '' if unparsable."""
    i = name.find(" = ")
    if i < 0:
        return ""
    j = _skip_type(name, i + 3)
    k = name.find("(", j)
    return name[j:k].strip() if k > 0 else ""


def array_types(text: str) -> list[tuple[int, tuple[int, ...]]]:
    """(itemsize, dims) of every array type written in ``text``."""
    out = []
    for dt, dims in _TYPE.findall(text):
        if dt in _ITEMSIZE:
            out.append((
                _ITEMSIZE[dt],
                tuple(int(d) for d in dims.split(",") if d),
            ))
    return out


def result_and_operands(name: str):
    """(result types, operand types) of an op's HLO text."""
    i = name.find(" = ")
    j = _skip_type(name, i + 3)
    k = name.find("(", j)
    depth, end = 0, len(name)
    for m in range(k, len(name)):
        depth += {"(": 1, ")": -1}.get(name[m], 0)
        if depth == 0:
            end = m
            break
    return array_types(name[i + 3 : j]), array_types(name[k + 1 : end])


# -- intervals ---------------------------------------------------------------


def union(intervals) -> list[tuple[float, float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(merged) -> float:
    return sum(e - s for s, e in merged)


def clip(merged, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in merged if e > lo and s < hi]


def subtract(a, b) -> list[tuple[float, float]]:
    """Parts of merged intervals ``a`` not covered by merged ``b``."""
    out = []
    for s, e in a:
        cur = s
        for bs, be in b:
            if be <= cur or bs >= e:
                continue
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


# -- reductions --------------------------------------------------------------


def in_window(trace: Trace, events) -> list[Event]:
    lo, hi = trace.window
    return [e for e in events if e.end > lo and e.start < hi]


def work_events(trace: Trace, device: str) -> list[Event]:
    """Ops that did work on ``device`` inside the window (no containers)."""
    return [
        e for e in in_window(trace, trace.devices[device])
        if opcode(e.name) not in CONTAINERS
    ]


def busy(trace: Trace, device: str) -> list[tuple[float, float]]:
    """Merged intervals in which an op ran on ``device``, in the window."""
    return clip(
        union((e.start, e.end) for e in work_events(trace, device)),
        *trace.window,
    )


def busy_s(trace: Trace) -> float:
    """Device busy seconds in the window, averaged over the devices."""
    if not trace.devices:
        return 0.0
    return sum(
        length(busy(trace, d)) for d in trace.devices
    ) * 1e-9 / len(trace.devices)


def idle_share(trace: Trace) -> float | None:
    """Percent of the window in which no op ran, averaged over devices."""
    busy_sec = busy_s(trace)
    if not trace.devices or busy_sec <= 0.0:
        return None
    return 100.0 * (1.0 - busy_sec / trace.window_s)


def _short(name: str, limit: int = 96) -> str:
    """``root type opcode`` of an op's HLO text, e.g. ``_lambda_
    f32[8,64,64,128] custom-call``."""
    i = name.find(" = ")
    if i < 0:
        return name[:limit]
    root = re.sub(r"\.\d+$", "", name[:i].lstrip("%"))
    j = _skip_type(name, i + 3)
    types = re.sub(r"\{[^}]*\}", "", name[i + 3 : j])
    return f"{root} {types} {opcode(name)}"[:limit]


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The ops that took most device time (seconds summed over devices)
    and the longest idle gaps of the first device, each named by the
    innermost host span that covers its middle."""
    per_op: dict[str, float] = {}
    for d in trace.devices:
        for e in work_events(trace, d):
            k = _short(e.name)
            per_op[k] = per_op.get(k, 0.0) + e.dur * 1e-9
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    gaps = []
    if trace.devices:
        first = sorted(trace.devices)[0]
        b = busy(trace, first)
        idle = subtract([trace.window], b)
        for s, e in sorted(idle, key=lambda iv: iv[0] - iv[1])[:top]:
            mid = 0.5 * (s + e)
            cover = [
                h for h in trace.host
                if h.start <= mid <= h.end and h.name != WINDOW_SPAN
            ]
            what = min(cover, key=lambda h: h.dur).name if cover else "none"
            gaps.append([what[:96], (e - s) * 1e-9])
    return {"device_ops": [list(kv) for kv in ops], "idle_gaps": gaps}
