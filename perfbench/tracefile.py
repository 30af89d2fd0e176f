"""Reduction of a profiler trace to the numbers the metrics read.

The trace is JAX's ``.xplane.pb`` as ``jax.profiler.ProfileData`` reads
it.  On a TPU each chip is a plane ``/device:TPU:<n>`` whose ``XLA Ops``
line holds one event per HLO instruction run (its name is the
instruction's text: ``%name = shape opcode(...)``) and whose ``Async XLA
Ops`` line holds the spans of asynchronous instructions (copies,
collectives) from start to done.  The host plane ``/host:CPU`` holds the
benchmark's own ``TraceAnnotation`` spans.  Device and host events share
one clock (nanoseconds), to within about a millisecond.

:func:`reduce_trace` works on plain tuples so that tests can hand it a
synthesised trace.
"""

from __future__ import annotations

import collections
import re

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
# instructions that only hold other instructions; their spans are not work
CONTAINERS = {"while", "conditional", "call"}
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all", "collective-broadcast")
_OPCODE = re.compile(r"[\]\}\)] ([a-z][a-z0-9\-]*)\(")
_SHAPE = re.compile(r"([a-z]+[0-9]*)\[([0-9,]*)\]")


def instruction(text: str) -> tuple[str, str, str]:
    """``(name, opcode, first output shape)`` of an ``XLA Ops`` event's
    text; the name loses its ``%`` and its ``.<n>`` suffix."""
    head, _, rest = text.partition(" = ")
    name = re.sub(r"\.\d+$", "", head.lstrip("%").strip())
    m = _OPCODE.search(rest)
    shape = _SHAPE.search(rest)
    return (name, m.group(1) if m else "",
            shape.group(0) if shape else "")


def is_collective(name: str, opcode: str) -> bool:
    return any(c in opcode or name.startswith(c) for c in COLLECTIVES)


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted ``(start, end)`` intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list[tuple[float, float]]:
    """Parts of the merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def planes_from_profile(pd) -> tuple[dict, list]:
    """``({device: {"ops": [...], "async": [...]}}, host spans)`` from a
    ``ProfileData``; events as ``(start_ns, end_ns, text)``."""
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {line.name: line for line in plane.lines}
            dev = {}
            for key, line_name in (("ops", OPS_LINE), ("async", ASYNC_LINE)):
                line = lines.get(line_name)
                dev[key] = [] if line is None else [
                    (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                    for ev in line.events]
            devices[plane.name] = dev
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend((ev.start_ns, ev.start_ns + ev.duration_ns,
                             ev.name) for ev in line.events)
    return devices, host


def reduce_trace(devices: dict, host: list, *, window_span: str,
                 host_spans: tuple[str, ...], top: int = 10) -> dict:
    """Busy, idle, kernel and collective times of a traced window.

    ``window_span`` names the host span that marks each measured step;
    the window runs from the first such span's start to the last one's
    end.  Gaps in the device's busy time are named by the host span of
    ``host_spans`` that overlaps them most.  Times are in seconds.
    """
    steps = [(s, e) for s, e, n in host if n == window_span]
    if not steps:
        raise ValueError(f"no {window_span!r} spans in the trace")
    lo, hi = min(s for s, _ in steps), max(e for _, e in steps)
    window = (hi - lo) * 1e-9
    marks = sorted((s, e, n) for s, e, n in host if n in host_spans)
    busy, exposed, coll = [], [], []
    custom: dict[str, list] = collections.defaultdict(list)
    op_time: dict[str, float] = collections.defaultdict(float)
    gaps: list[tuple[float, str]] = []
    for dev_name in sorted(devices):
        dev = devices[dev_name]
        work, comm = [], []
        for s, e, text in dev["ops"]:
            if e <= lo or s >= hi:
                continue
            name, opcode, shape = instruction(text)
            if opcode in CONTAINERS or opcode.endswith("-done") or \
                    opcode.endswith("-start"):
                if is_collective(name, opcode):
                    comm.append((s, e))
                continue
            if is_collective(name, opcode):
                comm.append((s, e))
                continue
            work.append((s, e))
            label = text.partition(" = ")[0].lstrip("%")
            op_time[f"{label} {shape}"] += (min(e, hi) - max(s, lo)) * 1e-9
            if opcode == "custom-call" and "tpu_custom_call" in text:
                custom[name].append((shape, (min(e, hi) - max(s, lo)) * 1e-9))
        for s, e, text in dev["async"]:
            name, opcode, _ = instruction(text)
            if is_collective(name, opcode):
                comm.append((s, e))
        w = clip(union(work), lo, hi)
        c = clip(union(comm), lo, hi)
        busy.append(length(union(w + c)) * 1e-9)
        exposed.append(length(subtract(c, w)) * 1e-9)
        coll.append(length(c) * 1e-9)
        if dev_name == min(devices):
            gaps = _named_gaps(union(w + c), lo, hi, marks)
    n = max(len(devices), 1)
    ops = sorted(((k, v / n) for k, v in op_time.items()),
                 key=lambda kv: -kv[1])[:top]
    gaps = sorted(gaps, key=lambda g: -g[1])[:top]
    return {"window_s": window, "devices": len(devices),
            "busy_s": busy, "collective_s": coll,
            "collective_exposed_s": exposed,
            "custom_calls": dict(custom), "steps": len(steps),
            "breakdown": {"device_ops": [[k, v] for k, v in ops],
                          "idle_gaps": [[k, v] for k, v in gaps]}}


def _named_gaps(busy, lo, hi, marks) -> list[tuple[str, float]]:
    """Idle gaps of one device inside ``[lo, hi]``, each named by the host
    span that overlaps it most (``other`` where none does)."""
    edges = [lo] + [x for s, e in busy for x in (s, e)] + [hi]
    out = []
    for s, e in zip(edges[0::2], edges[1::2]):
        if e <= s:
            continue
        best, name = 0.0, "other"
        for ms, me, mn in marks:
            if ms >= e:
                break
            ov = min(e, me) - max(s, ms)
            if ov > best:
                best, name = ov, mn
        out.append((name, (e - s) * 1e-9))
    return out
