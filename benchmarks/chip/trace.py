"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to device busy and
idle time, the device operations that took longest (by self time, so a
loop is not counted again for its body), and the longest idle gaps,
each named by the host span it fell in.

Device operations are the events of the ``ops_line`` line on every
plane whose name starts with ``device_prefix`` (on a TPU: the ``XLA
Ops`` line of ``/device:TPU:<n>``); busy time is the union of their
intervals inside the window, averaged over the devices.  Whole programs
are the events of ``modules_line``; each is matched to the host span
that dispatched it (the engine annotates its steps ``decode`` and
``mixed``), which gives each kind of step its device time.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # (start_ns, end_ns)

TPU = {"device_prefix": "/device:TPU:", "ops_line": "XLA Ops",
       "modules_line": "XLA Modules", "host_plane": "/host:CPU"}


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str):
    """(``ProfileData``, the file's bytes) of one ``.xplane.pb``: the
    bytes hold what ``ProfileData`` does not expose, each op's op-name
    path (``scopes.op_paths``)."""
    from jax.profiler import ProfileData
    raw = Path(path).read_bytes()
    return ProfileData.from_serialized_xspace(raw), raw


def _events(line) -> List[Tuple[str, float, float]]:
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def device_lines(pd, device_prefix: str, line_name: str):
    """{plane name: [(op, start, end)]} of each device's ``line_name``."""
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith(device_prefix):
            continue
        evs = []
        for line in plane.lines:
            # a CPU client's line carries an id: "<name>/<id>"
            if line.name.split("/")[0] == line_name:
                evs.extend(_events(line))
        out[plane.name] = sorted(evs, key=lambda e: e[1])
    return out


def host_spans(pd, host_plane: str, names: Sequence[str]):
    """[(name, start, end)] of host events whose name is in ``names``."""
    want = set(names)
    out = []
    for plane in pd.planes:
        if plane.name != host_plane:
            continue
        for line in plane.lines:
            out.extend(e for e in _events(line) if e[0] in want)
    return sorted(out, key=lambda e: e[1])


def union(intervals: Sequence[Interval], lo: float, hi: float
          ) -> List[Interval]:
    """Merged intervals, clipped to [lo, hi]."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The complement of merged ``busy`` inside [lo, hi]."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def short_name(op: str) -> str:
    """``%fusion.221 fusion bf16[4,8,8192,600]`` from an HLO op's full
    text (a TPU trace names each op by its whole instruction)."""
    if " = " not in op:
        return op
    lhs, rhs = op.split(" = ", 1)
    m = re.search(r"[\]})] ([a-z][\w-]*)\(", rhs)
    kind = m.group(1) if m else ""
    shape = "tuple" if rhs.startswith("(") else rhs.split("{")[0]
    return " ".join(x for x in (lhs, kind, shape) if x)


def self_times(evs: Sequence[Tuple[str, float, float]], lo: float,
               hi: float) -> Dict[str, float]:
    """Nanoseconds each op ran inside [lo, hi] less the ops nested in it
    (a loop's body ops sit inside the loop's own event)."""
    out: Dict[str, float] = {}
    stack: List[list] = []              # [end, name, duration, children]

    def close(item):
        out[item[1]] = out.get(item[1], 0.0) + item[2] - item[3]

    for name, s, e in sorted(((n, max(s, lo), min(e, hi)) for n, s, e in evs
                              if min(e, hi) > max(s, lo)),
                             key=lambda x: (x[1], -x[2])):
        while stack and s >= stack[-1][0]:
            close(stack.pop())
        if stack:
            stack[-1][3] += e - s
        stack.append([e, name, e - s, 0.0])
    while stack:
        close(stack.pop())
    return out


def span_at(spans: Sequence[Tuple[str, float, float]], t: float,
            default: str = "host:unannotated") -> str:
    """Name of the innermost (shortest) host span covering time ``t``."""
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else default


def step_device_seconds(modules: Sequence[Tuple[str, float, float]],
                        steps: Sequence[Tuple[str, float, float]]
                        ) -> Dict[str, List[float]]:
    """Device seconds of each annotated step: the longest program that
    starts between a step's dispatch and the next step's dispatch."""
    starts = [m[1] for m in modules]
    out: Dict[str, List[float]] = {}
    for i, (kind, s, _) in enumerate(steps):
        nxt = steps[i + 1][1] if i + 1 < len(steps) else float("inf")
        lo, hi = bisect.bisect_left(starts, s), bisect.bisect_left(starts, nxt)
        if hi > lo:
            dur = max(m[2] - m[1] for m in modules[lo:hi])
            out.setdefault(kind, []).append(dur * 1e-9)
    return out


def reduce(pd, *, window: Optional[Interval] = None,
           step_names: Sequence[str] = ("decode", "mixed"),
           span_names: Sequence[str] = (), top: int = 10,
           device_prefix: str = TPU["device_prefix"],
           ops_line: str = TPU["ops_line"],
           modules_line: str = TPU["modules_line"],
           host_plane: str = TPU["host_plane"]) -> Optional[dict]:
    """Busy and idle time over ``window`` (default: from the first to
    the last step span), top device ops and the longest idle gaps.
    Returns None when the trace holds no device operation."""
    ops = {k: v for k, v in device_lines(pd, device_prefix,
                                         ops_line).items() if v}
    if not ops:
        return None
    steps = host_spans(pd, host_plane, step_names)
    spans = host_spans(pd, host_plane, tuple(step_names) + tuple(span_names))
    if window is None:
        if steps:
            window = (steps[0][1], max(e for _, _, e in spans))
        else:
            window = (min(v[0][1] for v in ops.values()),
                      max(e for v in ops.values() for _, _, e in v))
    lo, hi = window
    busy_ns, per_op, idle = 0.0, {}, []
    for evs in ops.values():
        merged = union([(s, e) for _, s, e in evs], lo, hi)
        busy_ns += sum(e - s for s, e in merged)
        for name, t in self_times(evs, lo, hi).items():
            key = short_name(name)
            per_op[key] = per_op.get(key, 0.0) + t
        idle.extend(gaps(merged, lo, hi))
    n = len(ops)
    by_span: Dict[str, float] = {}
    for s, e in idle:
        key = span_at(spans, (s + e) / 2)
        by_span[key] = by_span.get(key, 0.0) + (e - s)
    longest = sorted(idle, key=lambda g: g[0] - g[1])[:top]
    modules = sorted((ev for evs in device_lines(
        pd, device_prefix, modules_line).values() for ev in evs),
        key=lambda m: m[1])
    window_s = (hi - lo) * 1e-9
    busy_s = busy_ns / n * 1e-9
    return {
        "devices": n,
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "top_ops": sorted(((k, v / n * 1e-9) for k, v in per_op.items()),
                          key=lambda kv: -kv[1])[:top],
        "idle_gaps": [(span_at(spans, (s + e) / 2), (e - s) * 1e-9)
                      for s, e in longest],
        "idle_by_span": {k: v / n * 1e-9 for k, v in by_span.items()},
        "step_device_s": step_device_seconds(modules, steps),
        "steps": len(steps),
    }
