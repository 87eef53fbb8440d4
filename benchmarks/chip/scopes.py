"""Device time by named scope, and idle gaps named by the engine's host
spans, from a kept ``jax.profiler`` trace (``.xplane.pb``) of decode
steps:

    python3 benchmarks/chip/scopes.py <trace.xplane.pb>

prints one JSON object: ``trace.reduce``'s reading of the trace, with
``idle_gaps`` and ``idle_by_span`` named by every host span of
``repro.serving.obs.profiling.HOST_SPANS`` (``reduce`` names them by
the step spans alone), plus ``scope_device_s`` (seconds of device self
time per named scope, and ``unscoped``), ``programs_in_window`` (step
programs counted by the share of each inside the window),
``scope_ms_per_step`` (where every step in the window is a decode step)
and ``span_ms`` (host milliseconds per span).  A traced run of the
harness reads its trace the same way (``harness.read_trace``) and hands
the reading to the metric readers; a run deletes its trace, so keep one
by serving a window with ``harness.serve_window(..., profile_dir=DIR)``.

An op's scope is the innermost segment of its op-name path that has the
form of a named scope (:data:`SCOPE`), so a scope the program adds is
attributed with no edit here.  The path is the ``tf_op`` stat of the
op's event metadata, which ``ProfileData`` does not expose:
:func:`op_paths` reads it from the file's protobuf fields.  An
executable loaded from JAX's persistent compile cache carries the
metadata of the program it was compiled from, and the cache key leaves
metadata out unless ``jax_compilation_cache_include_metadata_in_key`` is
set: capture traces with it set (the harness's runs do), or another
commit's names appear.
"""

from __future__ import annotations

import json
import re
import statistics
import sys
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

if __name__ == "__main__":
    ROOT = Path(__file__).resolve().parents[2]
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import trace as tr  # noqa: E402

# A named scope of the program (``jax.named_scope``): ``<area>.<phase>``
# in lower case, such as ``socket.score`` or ``layer.mlp``.  JAX's own
# segments (``jit(step)``, ``while``, ``dot_general``) and XLA's op
# names (``fusion.221``, ``dot_general.1``) never have that form.
SCOPE = re.compile(r"[a-z][a-z0-9_]*\.[a-z][a-z0-9_]*")

Span = Tuple[str, float, float]


def _varint(b, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(b):
    """(field number, value) of each field of one protobuf message: an
    int for a varint, the bytes for any other wire type."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = b[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield field, v


def _text(b) -> str:
    return bytes(b).decode("utf-8", "replace")


def op_paths(raw: bytes, stat: str = "tf_op") -> Dict[str, Dict[str, str]]:
    """{plane name: {op name: its ``stat``}} from a serialized XSpace.

    The fields read (tsl ``xplane.proto``): ``XSpace.planes`` (1);
    ``XPlane.name`` (2), ``.event_metadata`` (4) and ``.stat_metadata``
    (5), maps whose entries hold the value in field 2;
    ``XEventMetadata.name`` (2) and ``.stats`` (5);
    ``XStatMetadata.id`` (1) and ``.name`` (2); ``XStat.metadata_id`` (1)
    and its string, ``str_value`` (5) or ``ref_value`` (7, the id of a
    stat metadata whose name is the string)."""
    out: Dict[str, Dict[str, str]] = {}
    for field, plane in _fields(memoryview(raw)):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for pf, v in _fields(plane):
            if pf == 2:
                name = _text(v)
            elif pf == 4:
                events.append(v)
            elif pf == 5:
                meta = dict(_fields(dict(_fields(v)).get(2, b"")))
                stat_names[meta.get(1, 0)] = _text(meta.get(2, b""))
        paths = {}
        for entry in events:
            op, value = "", None
            for mf, mv in _fields(dict(_fields(entry)).get(2, b"")):
                if mf == 2:
                    op = _text(mv)
                elif mf == 5:
                    st = dict(_fields(mv))
                    if stat_names.get(st.get(1)) != stat:
                        continue
                    if 5 in st:
                        value = _text(st[5])
                    elif 7 in st:
                        value = stat_names.get(st[7])
            if value:
                paths[op] = value
        if paths:
            out[name] = paths
    return out


def scope_of(path: str) -> str:
    """The innermost named scope in an op-name path such as
    ``jit(step)/while/body/socket.score/socket.hash/div:``, else
    ``unscoped``."""
    for part in reversed(path.split("/")):
        name = part.split(":")[0]
        if SCOPE.fullmatch(name):
            return name
    return "unscoped"


def programs_in_window(modules: Sequence[Span], lo: float, hi: float
                       ) -> float:
    """Programs inside [lo, hi], each counted by the share of it that lies
    inside (not matched to the step spans, since the host and device
    clocks of a trace can disagree by more than a dispatch takes)."""
    return sum((min(e, hi) - max(s, lo)) / (e - s) for _, s, e in modules
               if min(e, hi) > max(s, lo))


def breakdown(pd, paths: Dict[str, Dict[str, str]], *,
              label_names: Sequence[str] = (), top: int = 10,
              step_names: Sequence[str] = ("decode", "mixed"),
              device_prefix: str = tr.TPU["device_prefix"],
              ops_line: str = tr.TPU["ops_line"],
              modules_line: str = tr.TPU["modules_line"],
              host_plane: str = tr.TPU["host_plane"]) -> Optional[dict]:
    """``trace.reduce(pd)`` with its gaps named by the step spans and
    ``label_names``, plus device time by scope and host time by span
    (the keys of the module docstring).  None where ``reduce`` is."""
    kw = dict(step_names=step_names, device_prefix=device_prefix,
              ops_line=ops_line, modules_line=modules_line,
              host_plane=host_plane, top=top)
    steps = tr.host_spans(pd, host_plane, step_names)
    if not steps:
        return tr.reduce(pd, **kw)
    # reduce's own window, so that label_names only name the gaps
    lo, hi = steps[0][1], max(e for _, _, e in steps)
    red = tr.reduce(pd, window=(lo, hi), span_names=label_names, **kw)
    if red is None:
        return None
    ops = {k: v for k, v in tr.device_lines(pd, device_prefix,
                                            ops_line).items() if v}
    n = len(ops)
    by_scope: Dict[str, float] = {}
    for plane, evs in ops.items():
        plane_paths = paths.get(plane, {})
        for name, t in tr.self_times(evs, lo, hi).items():
            scope = scope_of(plane_paths.get(name, ""))
            by_scope[scope] = by_scope.get(scope, 0.0) + t
    modules = [ev for evs in tr.device_lines(
        pd, device_prefix, modules_line).values() for ev in evs]
    counted = programs_in_window(modules, lo, hi) / n
    span_ms: Dict[str, List[float]] = {}
    for name, s, e in tr.host_spans(pd, host_plane,
                                    tuple(step_names) + tuple(label_names)):
        if e >= lo and s <= hi:
            span_ms.setdefault(name, []).append((e - s) * 1e-6)
    scope_s = {k: v / n * 1e-9 for k, v in by_scope.items()}
    decode_only = {k for k, _, _ in steps} == {"decode"}
    return dict(red, **{
        "scope_device_s": scope_s,
        "programs_in_window": counted,
        "scope_ms_per_step": {k: 1e3 * v / counted
                              for k, v in scope_s.items()}
        if decode_only and counted else None,
        "span_ms": {k: {"n": len(v), "median": statistics.median(v),
                        "max": max(v)} for k, v in span_ms.items()},
    })


def ms_per_decode_step(ctx: dict, names: Sequence[str]) -> Optional[float]:
    """Device milliseconds a decode step spends in the scopes ``names``,
    from a metric reader's context: their summed ``scope_device_s`` over
    ``programs_in_window``.  None unless every step of the window is a
    decode step and the trace names one of the scopes."""
    red = ctx["trace"] or {}
    by_scope = red.get("scope_device_s") or {}
    programs = red.get("programs_in_window")
    steps = ctx["step_events"]
    if not programs or not steps or any(e["kind"] != "decode"
                                        for e in steps) \
            or not any(n in by_scope for n in names):
        return None
    return 1e3 * sum(by_scope.get(n, 0.0) for n in names) / programs


def load(path: str):
    """(``ProfileData``, :func:`op_paths`) of one ``.xplane.pb``."""
    pd, raw = tr.load(path)
    return pd, op_paths(raw)


def main(argv: List[str]) -> int:
    import argparse
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("xplane")
    args = p.parse_args(argv)
    from benchmarks.chip.harness import read_trace
    print(json.dumps(read_trace(args.xplane)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
