"""Reduction of a profiler trace to the device's busy time, the operations
that took it, and the idle gaps by what the host was doing.

The traced slice is the host annotation ``bench.trace_window``.  Busy time
is the union of the device's operation intervals inside it, averaged over
the chips that ran anything; idle is the rest.  Each idle gap is named by
the harness annotation (``bench.step``, ``bench.submit``,
``bench.wait_arrival``, ``bench.outputs``) that covers most of it, or
``other``.  An operation is named ``<program>/<op>`` after the XLA module
that encloses it, with its HLO text cut to the result and operand types;
an operation that encloses others (a loop) counts only through them.

``reduce_events`` works on plain event lists, which
``bench.harness.layers.events_from_xplane`` reads from the profiler's
``.xplane.pb``; they are also the form of the recorded traces the tests
keep.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict

WINDOW = "bench.trace_window"
HOST_SPANS = ("bench.step", "bench.submit", "bench.wait_arrival", "bench.outputs")
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_OPERAND = re.compile(r"\s*%[\w.\-]+")


def op_name(text: str, width: int = 160) -> str:
    """``%fusion.3 = f32[4,64] fusion(bf16[4,9216,64], s32[])`` from an HLO
    instruction's text: layouts, operand names and attributes dropped."""
    name, eq, rest = text.partition(" = ")
    if not eq:
        return text[:width]
    rest = _OPERAND.sub("", _LAYOUT.sub("", rest.split(", kind=")[0].split(", condition=")[0]))
    return f"{name} = {rest.strip()}"[:width]


def _union(intervals, lo, hi):
    """Disjoint sorted intervals covering ``intervals`` clipped to [lo, hi]."""
    merged = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _label(gap, host, starts):
    """The host span (sorted by start, ``starts`` their starts) that covers
    most of ``gap``; the harness's spans follow one another on one thread."""
    best, name = 0, "other"
    i = bisect.bisect_left(starts, gap[1]) - 1
    while i >= 0 and host[i][2] > gap[0]:
        n, s, e = host[i]
        overlap = min(e, gap[1]) - max(s, gap[0])
        if overlap > best:
            best, name = overlap, n
        i -= 1
    return name


def _module_of(op, modules, starts):
    """The module (sorted by start, ``starts`` their starts) holding ``op``."""
    i = bisect.bisect_right(starts, op[1]) - 1
    if i >= 0 and op[2] <= modules[i][2]:
        return modules[i][0]
    return None


def reduce_events(ev: dict, top: int = 10) -> dict:
    """busy_s, window_s, device_ops and idle_gaps of the traced slice."""
    wins = [(s, e) for n, s, e in ev["host"] if n == WINDOW]
    if not wins:
        raise ValueError(f"the trace holds no {WINDOW} span")
    lo, hi = wins[0]
    chips = {c: ops for c, ops in ev["ops"].items() if _union([o[1:] for o in ops], lo, hi)}
    if not chips:
        raise ValueError("no device operation ran inside the traced slice")
    busy, gaps, op_time = 0.0, [], defaultdict(float)
    host = sorted((h for h in ev["host"] if h[0] != WINDOW), key=lambda h: h[1])
    host_starts = [h[1] for h in host]
    for chip, ops in chips.items():
        merged = _union([o[1:] for o in ops], lo, hi)
        busy += sum(e - s for s, e in merged)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, _label((s, e), host, host_starts)))
        mods = sorted(ev["modules"].get(chip, []), key=lambda m: m[1])
        starts = [m[1] for m in mods]
        ops = sorted(ops, key=lambda o: (o[1], -o[2]))
        for i, op in enumerate(ops):
            if i + 1 < len(ops) and ops[i + 1][1] < op[2]:
                continue  # encloses the next operation: count its leaves
            d = min(op[2], hi) - max(op[1], lo)
            if d > 0:
                mod = _module_of(op, mods, starts)
                name = op_name(op[0])
                op_time[f"{mod}/{name}" if mod else name] += d / 1e9
    gaps.sort(key=lambda g: -g[0])
    return {
        "busy_s": busy / len(chips) / 1e9,
        "window_s": (hi - lo) / 1e9,
        "chips": len(chips),
        "device_ops": [[n, t] for n, t in sorted(op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[n, d / 1e9] for d, n in gaps[:top]],
    }
