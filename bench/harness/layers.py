"""The engine's spans and the model's layer scopes in a profiler trace, read
beside ``bench.harness.trace``'s reduction, and the per-layer numbers taken
from them.

``events_from_xplane`` reads the profiler's ``.xplane.pb`` into the plain
event lists ``trace.reduce_events`` takes (``{"ops": {chip: [...]},
"modules": {chip: [...]}, "host": [...]}``, every TPU's "XLA Ops" and "XLA
Modules" lines and the host spans as ``(name, start_ns, end_ns)``): the
harness's spans and the engine's (``repro.obs.trace``: ``engine.*``,
``prefill*``, ``decode.*``, ``swap``, ``replay*``, ``handoff.*``) among the
host spans, and each device operation as ``(name, start_ns, end_ns,
op_name)``.  ``op_name`` is the XLA metadata of the operation's HLO
instruction (``jit(decode_4x9216)/while/body/attention/dot_general``), read
from the HLO that the trace keeps for every module it ran ("Hlo Proto" stats
of the ``/host:metadata`` plane); it is ``""`` where the trace has none.

``reduce_events`` returns ``trace.reduce_events``'s numbers, unchanged, and:

- ``idle_gaps``: each idle gap named by the innermost host span covering
  most of it (more than half; else the one covering the most), so an engine
  span wins over the ``bench.*`` span around it;
- ``idle_by_span``: all idle time split by the innermost host span open at
  each instant of it (``other`` where none is), so a gap that runs from one
  round's output handling into the next round's dispatch counts for each;
- ``clock_shift_ms``: ``[lower, upper]`` bounds on what must be added to
  the device's timestamps to put them on the host's clock, from the decode
  rounds: a decode program cannot start before the host began its
  ``decode.dispatch``, and the round's ``decode.wait`` cannot end before
  the program does.  On one v5e the shift is a millisecond or two, as much
  as the host's own work between programs, so the gaps above are found and
  named with ``lower`` added to the device's events (the fastest launch
  taken as instant); ``None`` without the engine's spans, and nothing is
  moved;
- ``scopes``: ``[program, scope, seconds]``, the device time of each
  program's leaf operations by the innermost of ``SCOPES`` in their
  ``op_name`` (``""`` for none);
- ``runs``: executions of each program that start inside the slice;
- ``spans``: ``[count, seconds]`` of each host span that starts inside it.

A program is its module's name without the run's id: ``jit_decode_4x9216``.
"""
from __future__ import annotations

import bisect
import re
import statistics
from collections import defaultdict

from bench.harness import trace

# the layer scopes the model's programs carry (repro.models / repro.layers)
SCOPES = ("embed", "norm", "attention", "kv_write", "linear", "weight_quant",
          "act_quant", "mlp", "lm_head")
# first word of the engine's span names (repro.obs.trace users)
ENGINE_SPANS = ("engine", "prefill", "decode", "swap", "replay", "handoff")
DECODE = "jit_decode_"  # the decode program's module, any layout and shape
_RUN_ID = re.compile(r"\(\d+\)$")


def program(module: str) -> str:
    return _RUN_ID.sub("", module)


def _fields(buf):
    """(field number, value) of one serialized protobuf message; a
    length-delimited value is a memoryview, a varint an int."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {kind} is not read here")
        yield key >> 3, value


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _module_op_names(hlo_proto) -> dict:
    """{instruction name: op_name} of one serialized ``xla.HloProto``
    (hlo_module 1 > computations 3 > instructions 2 > name 1, metadata 7 >
    op_name 2)."""
    out = {}
    for f, module in _fields(hlo_proto):
        if f != 1:
            continue
        for g, comp in _fields(module):
            if g != 3:
                continue
            for h, inst in _fields(comp):
                if h != 2:
                    continue
                name = op = None
                for k, v in _fields(inst):
                    if k == 1:
                        name = bytes(v).decode()
                    elif k == 7:
                        op = next((bytes(x).decode() for j, x in _fields(v) if j == 2), "")
                if name is not None:
                    out[name] = op or ""
    return out


def hlo_op_names(raw: bytes) -> dict:
    """{module event name: {instruction name: op_name}} from the HLO protos
    in a serialized XSpace (planes 1 > event_metadata 4 > entry value 2 >
    name 2, stats 5 > bytes_value 6)."""
    out = {}
    for f, plane in _fields(memoryview(raw)):
        if f != 1:
            continue
        names = [bytes(v).decode() for g, v in _fields(plane) if g == 2]
        if names != ["/host:metadata"]:
            continue
        for g, entry in _fields(plane):
            if g != 4:
                continue
            meta = next((v for k, v in _fields(entry) if k == 2), None)
            if meta is None:
                continue
            name, protos = "", []
            for k, v in _fields(meta):
                if k == 2:
                    name = bytes(v).decode()
                elif k == 5:
                    protos += [x for j, x in _fields(v) if j == 6]
            for proto in protos:
                try:
                    ops = _module_op_names(proto)
                except (ValueError, IndexError, UnicodeDecodeError):
                    continue  # a bytes stat that is not an HloProto
                if ops:
                    out[name] = ops
    return out


def events_from_xplane(path: str) -> dict:
    """The harness's and the engine's host spans, and every TPU's modules
    and operations, each operation with its ``op_name``."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    data = ProfileData.from_serialized_xspace(raw)
    out = {"ops": {}, "modules": {}, "host": []}
    keep = set(trace.HOST_SPANS) | {trace.WINDOW}
    for plane in data.planes:
        m = trace._DEVICE.match(plane.name)
        for line in plane.lines:
            if m and line.name in ("XLA Ops", "XLA Modules"):
                key = "ops" if line.name == "XLA Ops" else "modules"
                out[key].setdefault(m.group(1), []).extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events)
            elif plane.name.startswith("/host"):
                out["host"].extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events
                    if e.name in keep or e.name.split(".")[0] in ENGINE_SPANS)
    return _name_ops(out, hlo_op_names(raw))


def _name_ops(ev: dict, tables: dict) -> dict:
    """``ev`` with each operation's ``op_name`` added from ``tables``:
    {module event name: {instruction name: op_name}}.  A module whose run
    id has no table takes its program's, where the program has one."""
    by_program = defaultdict(list)
    for name, table in tables.items():
        by_program[program(name)].append(table)
    for chip, ops in ev["ops"].items():
        mods = sorted(ev["modules"].get(chip, []), key=lambda x: x[1])
        starts = [x[1] for x in mods]
        named = []
        for op in ops:
            mod = trace._module_of(op, mods, starts)
            table = tables.get(mod) if mod else None
            if table is None and mod and len(by_program.get(program(mod), ())) == 1:
                table = by_program[program(mod)][0]
            inst = op[0].split(" = ", 1)[0].lstrip("%")
            named.append((*op, (table or {}).get(inst, "")))
        ev["ops"][chip] = named
    return ev


def scope_of(op_name: str) -> str:
    """The innermost layer scope in an op's metadata, or ``""``."""
    return next((p for p in reversed(op_name.split("/")) if p in SCOPES), "")


def _innermost(gap, host, starts, reach) -> str:
    """The host span covering more than half of ``gap`` with the shortest
    duration (spans on one thread nest), else the one covering the most.
    ``host`` is sorted by start; ``reach[i]`` is the latest end among
    ``host[:i + 1]``."""
    lo, hi = gap
    found = []
    i = bisect.bisect_left(starts, hi) - 1
    while i >= 0 and reach[i] > lo:
        name, s, e = host[i]
        overlap = min(e, hi) - max(s, lo)
        if overlap > 0:
            found.append((overlap, e - s, name))
        i -= 1
    if not found:
        return "other"
    most = [f for f in found if 2 * f[0] > hi - lo]
    if most:
        return min(most, key=lambda f: f[1])[2]
    return max(found, key=lambda f: (f[0], -f[1]))[2]


def _split(gap, host, starts, reach, into) -> None:
    """Add each piece of ``gap`` to ``into`` under the innermost host span
    open over it (``other`` where none is); arguments as ``_innermost``."""
    lo, hi = gap
    cover = []
    i = bisect.bisect_left(starts, hi) - 1
    while i >= 0 and reach[i] > lo:
        name, s, e = host[i]
        if e > lo:
            cover.append((max(s, lo), min(e, hi), e - s, name))
        i -= 1
    edges = sorted({lo, hi, *(c[0] for c in cover), *(c[1] for c in cover)})
    for a, b in zip(edges, edges[1:]):
        open_ = [(length, n) for s, e, length, n in cover if s <= a and b <= e]
        into[min(open_)[1] if open_ else "other"] += (b - a) / 1e9


def clock_shift(ev: dict, chip: str):
    """``(lower, upper)`` ns bounds on the shift that puts ``chip``'s
    timestamps on the host clock, from each decode program run and the
    ``decode.dispatch`` / ``decode.wait`` spans of its round; None without
    such spans.  A run is paired with the dispatch starting nearest to it;
    pairs whose offset lies more than half the runs' median spacing from
    the median offset are a run whose dispatch was not traced, and are
    left out."""
    dispatch = sorted(h[1] for h in ev["host"] if h[0] == "decode.dispatch")
    waits = sorted(h[2] for h in ev["host"] if h[0] == "decode.wait")
    runs = sorted((m[1], m[2]) for m in ev["modules"].get(chip, [])
                  if program(m[0]).startswith(DECODE))
    if not dispatch or not waits or not runs:
        return None
    pairs = []
    for start, end in runs:
        i = bisect.bisect_left(dispatch, start)
        d = min(dispatch[max(i - 1, 0):i + 1], key=lambda t: abs(t - start))
        j = bisect.bisect_left(waits, d)
        if j < len(waits):
            pairs.append((d - start, waits[j] - end))
    if not pairs:
        return None
    mid = statistics.median(p[0] for p in pairs)
    spacing = statistics.median(b[0] - a[0] for a, b in zip(runs, runs[1:])) if len(runs) > 1 \
        else float("inf")
    kept = [p for p in pairs if abs(p[0] - mid) <= spacing / 2]
    return max(p[0] for p in kept), min(p[1] for p in kept)


def reduce_events(ev: dict, top: int = 10) -> dict:
    """``trace.reduce_events`` with gaps named by the innermost span, and
    the device time of each program by layer scope."""
    plain = {c: [op[:3] for op in ops] for c, ops in ev["ops"].items()}
    out = trace.reduce_events(dict(ev, ops=plain), top)
    lo, hi = next((s, e) for n, s, e in ev["host"] if n == trace.WINDOW)
    host = sorted((h for h in ev["host"] if h[0] != trace.WINDOW), key=lambda h: h[1])
    starts = [h[1] for h in host]
    reach, latest = [], float("-inf")
    for h in host:
        latest = max(latest, h[2])
        reach.append(latest)
    gaps, scopes, runs, by_span = [], defaultdict(float), defaultdict(int), defaultdict(float)
    shifts = {}
    for chip, ops in ev["ops"].items():
        bounds = shifts[chip] = clock_shift(ev, chip)
        shift = bounds[0] if bounds else 0
        merged = trace._union([(op[1] + shift, op[2] + shift) for op in ops], lo, hi)
        if not merged:
            continue
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, _innermost((s, e), host, starts, reach)))
                _split((s, e), host, starts, reach, by_span)
        mods = sorted(ev["modules"].get(chip, []), key=lambda m: m[1])
        mod_starts = [m[1] for m in mods]
        for name, s, _ in mods:
            if lo <= s < hi:
                runs[program(name)] += 1
        ops = sorted(ops, key=lambda o: (o[1], -o[2]))
        for i, op in enumerate(ops):
            if i + 1 < len(ops) and ops[i + 1][1] < op[2]:
                continue  # encloses the next operation: count its leaves
            d = min(op[2], hi) - max(op[1], lo)
            mod = trace._module_of(op, mods, mod_starts)
            if d > 0 and mod:
                op_name = op[3] if len(op) > 3 else ""
                scopes[(program(mod), scope_of(op_name))] += d / 1e9
    spans = defaultdict(lambda: [0, 0.0])
    for name, s, e in host:
        if lo <= s < hi:
            spans[name][0] += 1
            spans[name][1] += (min(e, hi) - s) / 1e9
    gaps.sort(key=lambda g: -g[0])
    out.update(
        idle_gaps=[[n, d / 1e9] for d, n in gaps[:top]],
        idle_by_span=dict(sorted(by_span.items(), key=lambda kv: -kv[1])),
        scopes=[[p, s, t] for (p, s), t in sorted(scopes.items(), key=lambda kv: -kv[1])],
        runs=dict(runs),
        spans={n: v for n, v in sorted(spans.items(), key=lambda kv: -kv[1][1])},
        clock_shift_ms=next(([b[0] / 1e6, b[1] / 1e6] for b in shifts.values() if b), None),
    )
    return out


def decode_scope_ms(reduced, scope: str, gone: float = None):
    """Device milliseconds per decode-program run under ``scope`` in the
    traced slice.  ``None`` without a trace, without a decode run, or when
    no operation of the decode program carries a scope (the trace held no
    HLO to read them from); ``gone`` when scoped operations ran and none
    carried this one."""
    if not reduced or "scopes" not in reduced:
        return None
    runs = sum(n for p, n in reduced["runs"].items() if p.startswith(DECODE))
    decode = [(s, t) for p, s, t in reduced["scopes"] if p.startswith(DECODE)]
    if not runs or not any(s for s, _ in decode):
        return None
    total = sum(t for s, t in decode if s == scope)
    if not total:
        return gone
    return 1e3 * total / runs
