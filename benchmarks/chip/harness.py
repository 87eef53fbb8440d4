"""Run one cell of ``BENCHMARK.json`` once, on the chip this process holds.

A cell names a configuration (``configs/<config>.json``, whose
``block`` key names its block, ``blocks/<block>.py``, and whose
``reference`` key its plain reference, ``references/<reference>.py``), a
traffic mix (``traffic/<mix>.json``) and, by its own name, its serving
geometry and correctness limit (``cells/<cell>.json``); its metrics are
the entries of ``BENCHMARK.json`` whose ``workloads`` list it (or that
list none).  Each per-layer metric is read by ``metrics/<metric>.py``
from a context that holds, in a traced run, the trace's reading with
device time by named scope (``scopes.breakdown``).  Adding a
configuration (its block and its reference), a mix, a cell or a metric
adds files and entries only.

One run:

1. set-up: weights made on the device from the seed, the engine built
   and its two step programs warmed (served from the compile cache after
   a cell's first run), and every session's context prefilled
   (``setup_s`` runs from process start to here);
2. the window: ``ContinuousBatchingEngine.run`` serves the sessions in
   real time; the engine's ``iter_hook`` times every step and ends the
   run at the first step end past ``--seconds``;
3. with ``--trace 1`` the window runs under ``jax.profiler`` and the
   per-layer metrics are read from the trace (``read_trace``), the
   engine's step records and the step times; otherwise the end-to-end
   metrics are computed from the sessions' token times.  Every run keys
   the compile cache by op metadata, so that the trace's scope names are
   this program's;
4. ``correct``: the engine's state is freed, then the plain reference
   recomputes the logits at every served token of a seeded sample of
   sessions; the gap by which each served token's reference logit lies
   below the reference's best is taken, and the statistics of those
   gaps that the cell's file names (``GAP_STATISTICS``) are held against
   their limits.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import json
import os
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

from benchmarks.chip import flops, model, traffic

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]


class WindowClosed(Exception):
    """Raised from the engine's step hook to end a run."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    geometry: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    root: Path = HERE                  # where its files were found


def load_manifest(path: Path = REPO / "BENCHMARK.json") -> dict:
    return json.loads(Path(path).read_text())


def _listed(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, manifest: dict, root: Path = HERE) -> Cell:
    """Resolve a cell and every file it names, by name."""
    entry = next((w for w in manifest["workloads"] if w["name"] == name),
                 None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in the manifest")
    return Cell(
        name=name, chips=int(entry["chips"]),
        config=model.load_config(entry["config"], root),
        mix=traffic.load_mix(entry["traffic"], root),
        geometry=json.loads((root / "cells" / f"{name}.json").read_text()),
        end_to_end=[m for m in manifest["end_to_end"] if _listed(m, name)],
        per_layer=[m for m in manifest["per_layer"] if _listed(m, name)],
        root=root)


def metric_reader(name: str, root: Path = HERE) -> Callable:
    path = root / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.chip.metrics.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ----------------------------------------------------------- statistics

def percentile(xs, q: float) -> Optional[float]:
    """The ``q``-th percentile of all samples (linear between ranks)."""
    xs = np.asarray(list(xs), np.float64)
    return float(np.percentile(xs, q)) if xs.size else None


def token_gaps(walls_by_request, end: float) -> List[float]:
    """Every gap between consecutive tokens of one request, both emitted
    by ``end``."""
    out: List[float] = []
    for walls in walls_by_request:
        w = [x for x in walls if x <= end]
        out.extend(b - a for a, b in zip(w, w[1:]))
    return out


# ------------------------------------------------------------ the device

def device_report(jax) -> dict:
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def peak_bytes(jax) -> Optional[int]:
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    vals = [s["peak_bytes_in_use"] for s in stats if "peak_bytes_in_use" in s]
    return max(vals) if vals else None


def configure_cache(jax) -> str:
    """JAX's persistent compile cache: where ``JAX_COMPILATION_CACHE_DIR``
    says, else ``<checkout>/.jax_cache`` (a fixed path: the path is part
    of the cache key).  Keyed by op metadata too, which JAX leaves out by
    default: a cached executable could otherwise carry another program's
    scope names.  Every run keys alike, so a traced run loads what an
    untraced run compiled."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return path


class CompileCounter:
    """Counts backend compiles while ``on``."""

    def __init__(self, jax):
        self.on, self.count = False, 0

        def listen(event, duration, **_):
            if self.on and "backend_compile" in event:
                self.count += 1
        jax.monitoring.register_event_duration_secs_listener(listen)


# ------------------------------------------------------------ the engine

def requests_of(specs) -> list:
    from repro.serving.scheduler import Request
    out = []
    for s in specs:
        r = Request(prompt=[int(t) for t in s.prompt],
                    max_new_tokens=s.max_new_tokens)
        r.sample_key = np.zeros(2, np.uint32)   # greedy: never consumed
        out.append(r)
    return out


def build_engine(cell: Cell, weights):
    from repro.serving.engine import ContinuousBatchingEngine
    cfg = model.block(cell.config, cell.root).program_config(
        cell.config, serving=cell.geometry)
    engine = ContinuousBatchingEngine(cfg, params=weights)
    engine.warmup()
    return engine


def prefill(engine, sessions) -> None:
    """Serve ``sessions`` until each has its first token (set-up); this
    also runs every host-side conversion the window will."""
    def hook(eng, it):
        if all(r.t_first_token is not None for r in sessions):
            raise WindowClosed
    engine.iter_hook = hook
    try:
        engine.run(sessions, realtime=False)
    except WindowClosed:
        pass
    engine.iter_hook = None


@dataclasses.dataclass
class Window:
    seconds: float                     # measured length (engine clock)
    requests: list                     # the sessions served in it
    first_token: Dict[int, int]        # rid -> index of its first window token
    hooks: List[float]                 # time of each step end
    step_events: list                  # engine step records (traced runs)
    compiles: int


def serve_window(engine, sessions: list, seconds: float, *,
                 profile_dir: Optional[str] = None,
                 counter: Optional[CompileCounter] = None) -> Window:
    """Serve the prefilled sessions in real time for ``seconds``."""
    from repro.serving.obs import Observability
    first = {r.rid: len(r.token_walls) for r in sessions}
    hooks: List[float] = []
    if profile_dir is not None:
        engine.obs = Observability(profile_dir=profile_dir,
                                   profile_steps=1 << 30)
        # started here, before the window: the profiler's own start-up
        # (seconds, on a process's first trace) stays out of it
        engine.obs.profiler.maybe_start(0)

    def hook(eng, it):
        t = time.perf_counter() - t_call
        hooks.append(t)
        if t >= seconds:
            raise WindowClosed

    engine.iter_hook = hook
    if counter is not None:
        counter.on = True
    t_call = time.perf_counter()
    try:
        engine.run([], realtime=True)
    except WindowClosed:
        pass
    finally:
        if counter is not None:
            counter.on = False
        engine.iter_hook = None
        if engine.obs is not None and engine.obs.profiler is not None:
            engine.obs.profiler.stop()
    events = [e for e in engine.obs.tracer.events if e["ev"] == "step"] \
        if engine.obs is not None else []
    return Window(seconds=hooks[-1], requests=list(sessions),
                  first_token=first, hooks=hooks, step_events=events,
                  compiles=counter.count if counter is not None else 0)


def release(engine) -> None:
    """Free the engine's pool before the reference runs."""
    import jax
    for leaf in jax.tree_util.tree_leaves(engine.pages):
        leaf.delete()
    engine.pages = None
    gc.collect()


# ------------------------------------------------------------ the metrics

def window_walls(win: Window) -> List[List[float]]:
    return [r.token_walls[win.first_token[r.rid]:] for r in win.requests]


def end_to_end(win: Window) -> Dict[str, float]:
    """Every end-to-end quantity a cell may report, from token times:
    the rate over all tokens of the window, and the gaps between tokens
    (printed beside the metrics, not bounded)."""
    walls = window_walls(win)
    emitted = sum(sum(1 for w in ws if w <= win.seconds) for ws in walls)
    out = {"output_tok_s": emitted / win.seconds}
    gaps = token_gaps(walls, win.seconds)
    if gaps:
        out["itl_p50_ms"] = 1e3 * percentile(gaps, 50)
        out["itl_p95_ms"] = 1e3 * percentile(gaps, 95)
    return out


def window_steps(cfg: dict, win: Window, root: Path = HERE) -> List[dict]:
    """Each decode step of the window with the work it did (as the
    configuration's block counts it), from the step times and the token
    times: a token emitted by step ``s`` decoded at its request's context
    length then."""
    decode_lengths: List[List[int]] = [[] for _ in win.hooks]
    for r in win.requests:
        p = len(r.prompt)
        for i in range(max(win.first_token[r.rid], 1), len(r.token_walls)):
            s = int(np.searchsorted(win.hooks, r.token_walls[i]))
            if s < len(win.hooks):
                decode_lengths[s].append(p + i)
    steps = []
    for lengths in decode_lengths:
        f, b = flops.step(cfg, lengths, root=root)
        steps.append({"kind": "decode", "flops": f, "bytes": b,
                      "occupancy": len(lengths)})
    return steps


def read_trace(xplane: str, **planes) -> Optional[dict]:
    """A traced window's reading (``scopes.breakdown``): busy and idle
    time over the window the step spans set, idle gaps named by every
    host span the engine emits, and device time by named scope.
    ``planes`` names the trace's planes and lines where they are not a
    TPU's."""
    from benchmarks.chip import scopes
    from repro.serving.obs.profiling import HOST_SPANS
    pd, paths = scopes.load(xplane)
    return scopes.breakdown(pd, paths, label_names=HOST_SPANS, **planes)


# ------------------------------------------------------------ correctness

def sample_requests(win: Window, seed: int, n: int) -> list:
    """A seeded sample of served requests, the longest always in it."""
    served = [r for r in win.requests if r.generated]
    if len(served) <= n:
        return served
    longest = max(served, key=lambda r: len(r.prompt) + len(r.generated))
    rest = [r for r in served if r is not longest]
    pick = traffic.rng_for(seed, 7).choice(len(rest), n - 1, replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def logit_gaps(cell: Cell, weights, reqs: list) -> np.ndarray:
    """For every served token of ``reqs``: how far its reference logit
    lies below the reference's best at that position."""
    ref = importlib.import_module(
        f"benchmarks.chip.references.{cell.config['reference']}")
    return np.concatenate([ref.served_gaps(cell.config, weights, r.prompt,
                                           r.generated) for r in reqs])


GAP_STATISTICS = {"max_logit_gap": np.max, "mean_logit_gap": np.mean}


def check(cell: Cell, weights, reqs: list):
    """The numbers compared, each beside its limit: each statistic of
    ``GAP_STATISTICS`` that the cell's limits name (the widest gap, or,
    where that does not separate the control, the mean), over every
    served token of ``reqs``; and the gaps themselves."""
    gaps = logit_gaps(cell, weights, reqs)
    limits = cell.geometry["limits"]
    return {name: {"value": float(fn(gaps)), "limit": limits[name]}
            for name, fn in GAP_STATISTICS.items() if name in limits}, gaps


# ------------------------------------------------------------ one run

def run_cell(cell: Cell, seed: int, seconds: float, *, trace: bool,
             t_start: float, fault: Optional[Callable] = None) -> dict:
    """One run of ``cell``; returns the result line as a dict (its
    ``checks`` key last)."""
    import jax
    counter = CompileCounter(jax)
    weights = model.make_weights(cell.config, seed, cell.root)
    jax.block_until_ready(weights)
    engine = build_engine(cell, weights)
    if fault is not None:
        fault(engine)
    sessions = requests_of(traffic.generate(cell.mix, seed,
                                            cell.config["vocab_size"]))
    prefill(engine, sessions)
    setup_s = time.perf_counter() - t_start

    with tempfile.TemporaryDirectory(prefix="chipbench-trace-") as tmp:
        win = serve_window(engine, sessions, seconds,
                           profile_dir=tmp if trace else None,
                           counter=counter)
        mem = peak_bytes(jax)
        red = None
        if trace:
            from benchmarks.chip import trace as tr_mod
            red = read_trace(tr_mod.find_xplane(tmp))
    e2e = end_to_end(win)
    steps = window_steps(cell.config, win, cell.root)
    release(engine)
    del engine

    metrics: Dict[str, dict] = {}
    device = device_report(jax)
    device["memory_peak_bytes"] = mem
    breakdown = None
    if trace:
        from benchmarks.chip.peaks import peak
        if red is not None:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            breakdown = {"device_ops": [list(x) for x in red["top_ops"]],
                         "idle_gaps": [list(x) for x in red["idle_gaps"]]}
        ctx = {"window_s": win.seconds, "steps": steps,
               "step_events": win.step_events, "trace": red,
               "peak": peak(device["kind"]), "cell": cell}
        for m in cell.per_layer:
            v = metric_reader(m["name"], cell.root)(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e["setup_s"] = setup_s
        for m in cell.end_to_end:
            if e2e.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}

    limit_n = int(cell.geometry["limits"]["sample_requests"])
    t_ref = time.perf_counter()
    checks, gaps = check(cell, weights, sample_requests(win, seed, limit_n))
    reference_s = time.perf_counter() - t_ref
    correct = bool(checks) and all(
        c["limit"] is not None and c["value"] <= c["limit"]
        for c in checks.values())
    stalled = sum(1 for ws in window_walls(win)
                  if not any(w <= win.seconds for w in ws))
    info = {"setup_s": setup_s, "window_s": win.seconds,
            "compared_tokens": int(gaps.size),
            "max_logit_gap": float(gaps.max()),
            "mean_logit_gap": float(gaps.mean()),
            "steps": len(steps), "window_compiles": win.compiles,
            "reference_s": reference_s,
            **{k: v for k, v in e2e.items() if k != "setup_s"}}
    out = {"correct": bool(correct), "attempted": len(win.requests),
           "failed": stalled, "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["info"] = info
    out["checks"] = checks
    return out


def main(argv: List[str], t_start: float) -> int:
    import argparse
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    manifest = load_manifest()
    cell = load_cell(args.workload, manifest)
    import jax
    dev = device_report(jax)
    if dev["platform"] != "tpu" or dev["count"] < cell.chips:
        print(f"chipbench: {args.workload} needs {cell.chips} TPU chip(s); "
              f"JAX sees {dev['count']} {dev['platform']} device(s) "
              f"({dev['kind']})", file=sys.stderr)
        return 2
    configure_cache(jax)
    out = run_cell(cell, args.seed, args.seconds, trace=bool(args.trace),
                   t_start=t_start)
    print("chipbench info: " + json.dumps(out["info"]), file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0
