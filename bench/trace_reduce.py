"""Reduce a JAX profiler trace (``.xplane.pb``) to what the benchmark reports.

The benchmark wraps its calls into each layer in ``jax.profiler.
TraceAnnotation``s named ``bench.*``; the measured window is the one named
``bench.window``. From the trace this module takes, within that window:

- ``busy_s``: the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each ``/device:`` plane), averaged over
  the devices;
- ``device_ops``: device self time by operation (the time of the
  operations nested in a ``while`` or ``call`` is theirs), by short name,
  the largest first;
- ``idle_gaps``: the longest stretches in which no operation ran, each named
  by the innermost ``bench.*`` annotation open over most of it, else by
  those open at its start and end (``"<a>..<b>"``);
- ``step_device_s`` and ``step_programs``: device time of the XLA modules
  whose name holds the step's function name, and how many ran.
"""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]


def _union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(s: int, e: int, lo: int, hi: int) -> Optional[Interval]:
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def _label(gap: Interval, annotations: Sequence[Tuple[int, int, str]]) -> str:
    """The innermost annotation, the window aside, open over more than half
    of the gap; else those open at its two ends (``"<start>..<end>"``)."""
    s, e = gap
    over = [(ae - as_, name) for as_, ae, name in annotations
            if name != "bench.window"
            and min(e, ae) - max(s, as_) > 0.5 * (e - s)]
    if over:
        return min(over)[1]

    def at(t: int) -> str:
        open_ = [(ae - as_, name) for as_, ae, name in annotations
                 if as_ <= t <= ae]
        return min(open_)[1] if open_ else "none"
    a, b = at(s), at(e)
    return a if a == b else f"{a}..{b}"


def _self_times(ops: Sequence[Tuple[int, int, str]]) -> List[Tuple[str, int]]:
    """Each operation's time less that of the operations nested in it (a
    ``while`` holds its body's operations), by short name (``fusion.12``)."""
    evs = sorted(ops, key=lambda o: (o[0], -o[1]))
    own = [e - s for s, e, _ in evs]
    stack: List[Tuple[int, int]] = []   # (end, index) of open operations
    for i, (s, e, _) in enumerate(evs):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack and e <= stack[-1][0]:
            own[stack[-1][1]] -= e - s
        stack.append((e, i))
    return [(n.split(" = ")[0].lstrip("%"), t)
            for (_, _, n), t in zip(evs, own)]


def reduce_events(annotations: Sequence[Tuple[int, int, str]],
                  device_ops: Dict[str, Sequence[Tuple[int, int, str]]],
                  modules: Dict[str, Sequence[Tuple[int, int, str]]],
                  step_name: str, top: int = 10) -> Dict[str, object]:
    """The reduction over plain events ``(start_ns, end_ns, name)``:
    host annotations, and per device its operations and its modules."""
    windows = [(s, e) for s, e, n in annotations if n == "bench.window"]
    if not windows:
        raise ValueError("the trace holds no bench.window annotation")
    lo, hi = windows[0]
    by_name: Dict[str, float] = defaultdict(float)
    busy_total = 0
    gaps: List[Tuple[int, str]] = []
    step_ns, step_n = 0, 0
    for dev, ops in device_ops.items():
        clipped = [(c[0], c[1], name) for s, e, name in ops
                   for c in [_clip(s, e, lo, hi)] if c]
        for name, ns in _self_times(clipped):
            by_name[name] += ns / 1e9
        busy = _union([(s, e) for s, e, _ in clipped])
        busy_total += sum(e - s for s, e in busy)
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, _label((s, e), annotations)))
        for s, e, name in modules.get(dev, ()):
            c = _clip(s, e, lo, hi)
            if c and step_name in name:
                step_ns += c[1] - c[0]
                step_n += 1
    n_dev = max(1, len(device_ops))
    gaps.sort(key=lambda g: -g[0])
    ops_sorted = sorted(by_name.items(), key=lambda kv: -kv[1])
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_total / n_dev / 1e9,
        "devices": len(device_ops),
        "device_ops": [[n, t / n_dev] for n, t in ops_sorted[:top]],
        "idle_gaps": [[name, ns / 1e9] for ns, name in gaps[:top]],
        "step_device_s": step_ns / n_dev / 1e9,
        "step_programs": step_n // n_dev,
    }


def read_xplane(path: str) -> Tuple[list, dict, dict]:
    """Host ``bench.*`` annotations, and per device plane the ``XLA Ops``
    and ``XLA Modules`` events, from one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    annotations, ops, modules = [], {}, {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        s = int(ev.start_ns)
                        annotations.append((s, s + int(ev.duration_ns),
                                            ev.name))
        elif plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name not in ("XLA Ops", "XLA Modules"):
                    continue
                evs = [(int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                        ev.name) for ev in line.events]
                (ops if line.name == "XLA Ops" else modules)[plane.name] = evs
    return annotations, ops, modules


def reduce_trace(path: str, step_name: str) -> Dict[str, object]:
    annotations, ops, modules = read_xplane(path)
    if not ops:
        raise ValueError(f"{path}: no device plane with an XLA Ops line")
    return reduce_events(annotations, ops, modules, step_name)
