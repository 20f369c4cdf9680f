"""From a profiler trace to numbers: device busy intervals, device time by
operation name, and idle gaps with the host span that covers each.

The arithmetic works on a plain ``Trace`` (lists of ``(name, start_ns,
duration_ns)``), so a test can build one by hand; ``load_xplane`` fills one
from the ``.xplane.pb`` that ``jax.profiler`` writes.  Nothing here imports
the engine.
"""

from __future__ import annotations

import glob
import json
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

Event = Tuple[str, int, int]  # name, start_ns, duration_ns

WINDOW_SPAN = "bench.window"  # the harness's own span around the traced work
_DEVICE_PLANE = "/device:TPU:"
_OPS_LINE = "XLA Ops"
_HOST_PLANE = "/host:CPU"
TOP = 10
_MIN_GAP_NS = 100_000   # shorter gaps are launch latency, not a host stall
_MAX_GAPS = 2000
_NAME_CHARS = 120       # an XLA op's name is its whole HLO line


@dataclass
class Trace:
    device: Dict[str, List[Event]] = field(default_factory=dict)
    host: List[Event] = field(default_factory=list)


def load_peaks(path: str, device_kind: str) -> dict:
    """The peaks of ``device_kind``; a kind the table lacks is an error."""
    with open(path) as fh:
        table = json.load(fh)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}: "
                       f"add it with its source, do not default it")
    return table[device_kind]


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load_xplane(path: str) -> Trace:
    """Device planes keep their ``XLA Ops`` line (every line where a plane
    has none); the host plane keeps every span of every thread."""
    from jax.profiler import ProfileData
    trace = Trace()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(_DEVICE_PLANE):
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == _OPS_LINE] or lines
            trace.device[plane.name] = [
                (ev.name, int(ev.start_ns), int(ev.duration_ns))
                for ln in ops for ev in ln.events]
        elif plane.name == _HOST_PLANE:
            trace.host.extend(
                (ev.name, int(ev.start_ns), int(ev.duration_ns))
                for ln in plane.lines for ev in ln.events)
    return trace


def _merged(events: List[Event], lo: int, hi: int) -> np.ndarray:
    """Union of the events' intervals clipped to [lo, hi): an (n, 2) array
    of disjoint, ordered [start, end)."""
    if not events:
        return np.zeros((0, 2), dtype=np.int64)
    start = np.array([e[1] for e in events], dtype=np.int64)
    end = start + np.array([e[2] for e in events], dtype=np.int64)
    start, end = np.clip(start, lo, hi), np.clip(end, lo, hi)
    keep = end > start
    start, end = start[keep], end[keep]
    order = np.argsort(start, kind="stable")
    start, end = start[order], end[order]
    if not len(start):
        return np.zeros((0, 2), dtype=np.int64)
    reach = np.maximum.accumulate(end)
    first = np.concatenate(([True], start[1:] > reach[:-1]))
    last = np.concatenate((first[1:], [True]))
    return np.stack((start[first], reach[last]), axis=1)


def window_of(trace: Trace) -> Tuple[int, int]:
    """The harness's own window span, or else the extent of all events."""
    spans = [e for e in trace.host if e[0] == WINDOW_SPAN]
    if spans:
        return (min(e[1] for e in spans),
                max(e[1] + e[2] for e in spans))
    every = [e for evs in trace.device.values() for e in evs] + trace.host
    if not every:
        raise ValueError("the trace holds no event")
    return min(e[1] for e in every), max(e[1] + e[2] for e in every)


def _covering_span(host: Tuple[np.ndarray, np.ndarray, List[str]],
                   lo: int, hi: int) -> str:
    start, end, names = host
    inside = np.flatnonzero((start <= lo) & (end >= hi))
    if len(inside):
        return names[int(inside[np.argmin(end[inside] - start[inside])])]
    overlap = np.minimum(end, hi) - np.maximum(start, lo)
    if len(overlap) and overlap.max() > 0:
        return names[int(np.argmax(overlap))]
    return "(no host span)"


def reduce(trace: Trace, window: Optional[Tuple[int, int]] = None) -> dict:
    """``busy_s`` (mean over the chips), ``window_s``, ``device_ops`` and
    ``idle_gaps`` (each at most ``TOP`` of ``[name, seconds]``), and
    ``events`` (how many device events were read)."""
    lo, hi = window or window_of(trace)
    if hi <= lo:
        raise ValueError("empty window")
    chips = max(1, len(trace.device))
    busy_ns, ops, gaps = 0, {}, []
    for events in trace.device.values():
        merged = _merged(events, lo, hi)
        busy_ns += int((merged[:, 1] - merged[:, 0]).sum())
        for name, start, dur in events:
            clipped = min(start + dur, hi) - max(start, lo)
            if clipped > 0:
                ops[name] = ops.get(name, 0) + clipped
        edges = np.concatenate(([lo], merged.ravel(), [hi])).reshape(-1, 2)
        gaps.extend((int(b - a), int(a), int(b)) for a, b in edges
                    if b - a >= _MIN_GAP_NS)
    host = [e for e in trace.host if e[0] != WINDOW_SPAN and e[2] > 0]
    host_arrays = (np.array([e[1] for e in host], dtype=np.int64),
                   np.array([e[1] + e[2] for e in host], dtype=np.int64),
                   [e[0] for e in host])
    by_span: Dict[str, int] = {}
    for dur, a, b in sorted(gaps, reverse=True)[:_MAX_GAPS]:
        name = _covering_span(host_arrays, a, b)
        by_span[name] = by_span.get(name, 0) + dur

    def top(d: Dict[str, int]) -> list:
        return [[k[:_NAME_CHARS], v / chips / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

    return {"busy_s": busy_ns / chips / 1e9, "window_s": (hi - lo) / 1e9,
            "device_ops": top(ops), "idle_gaps": top(by_span),
            "events": sum(len(v) for v in trace.device.values())}
