"""Readings that a cell's correctness limit is set from, on the chip.

    python3 benchmarks/chip/calibrate.py <cell> --seconds <s> \
        --seeds <n> --control-seeds <m> [--first-seed <k>]

For each seed it serves the cell as a run does (same set-up, same
window at the cell's load) and reads the logit gaps of the served
tokens against the plain reference (``harness.logit_gaps``): the
program's readings.  On the first ``--control-seeds`` seeds it also
reads the control: the reference in the precision below the
configuration's, in the program's place, at the same prompts and
tokens.  The limit lies between the largest program reading and the
smallest control reading.  One JSON line per seed with the summaries
and every gap; all seeds share this process, so the programs compile
once.  With ``--write-limit`` the cell's file gets the limit that
``limit_from`` sets from these readings.
"""

from __future__ import annotations

import argparse
import gc
import importlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def summary(gaps) -> dict:
    """The statistics a limit may be set on, of one seed's gaps."""
    import numpy as np
    return {"mean": float(np.mean(gaps)), "max": float(np.max(gaps)),
            "p99": float(np.percentile(gaps, 99)),
            "nonzero": float(np.mean(np.asarray(gaps) > 0)),
            "tokens": int(len(gaps))}


def limit_from(rows, min_seeds: int = 12, min_controls: int = 3):
    """The statistic compared and its limit, from calibration rows: the
    widest gap where it separates, else the mean.  A statistic separates
    where the smallest control reading is 3x the largest program reading
    or more; the limit then lies 60% of the way from the lower reading to
    the upper, two significant digits.  ``None`` where none separates."""
    prog = [r["program"] for r in rows]
    ctl = [r["control"] for r in rows if "control" in r]
    if len(prog) < min_seeds or len(ctl) < min_controls:
        return None
    for stat in ("max", "mean"):
        lower = max(p[stat] for p in prog)
        upper = min(c[stat] for c in ctl)
        if upper >= 3 * lower:
            x = lower + 0.6 * (upper - lower)
            return f"{stat}_logit_gap", float(f"{x:.2g}")
    return None


def main(argv):
    p = argparse.ArgumentParser()
    p.add_argument("cell")
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--control-seeds", type=int, default=3)
    p.add_argument("--first-seed", type=int, default=3000000019)
    p.add_argument("--write-limit", action="store_true")
    args = p.parse_args(argv)

    import jax
    import numpy as np

    from benchmarks.chip import harness, model, traffic
    dev = harness.device_report(jax)
    if dev["platform"] != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    harness.configure_cache(jax)
    cell = harness.load_cell(args.cell, harness.load_manifest())
    ref = importlib.import_module(
        f"benchmarks.chip.references.{cell.config['reference']}")
    n = int(cell.geometry["limits"]["sample_requests"])
    rows = []
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t0 = time.perf_counter()
        weights = model.make_weights(cell.config, seed, cell.root)
        engine = harness.build_engine(cell, weights)
        sessions = harness.requests_of(traffic.generate(
            cell.mix, seed, cell.config["vocab_size"]))
        harness.prefill(engine, sessions)
        win = harness.serve_window(engine, sessions, args.seconds)
        harness.release(engine)
        del engine
        reqs = harness.sample_requests(win, seed, n)
        t1 = time.perf_counter()
        prog = harness.logit_gaps(cell, weights, reqs)
        t2 = time.perf_counter()
        row = {"cell": cell.name, "seed": seed,
               "program": summary(prog), "window_s": win.seconds,
               "serve_s": t1 - t0, "reference_s": t2 - t1}
        if i < args.control_seeds:
            ctl = np.concatenate([ref.control_gaps(
                cell.config, weights, r.prompt, r.generated) for r in reqs])
            row["control"] = summary(ctl)
            row["control_s"] = time.perf_counter() - t2
            row["control_gaps"] = [round(float(x), 6) for x in ctl]
        row["program_gaps"] = [round(float(x), 6) for x in prog]
        print(json.dumps(row), flush=True)
        rows.append(row)
        del weights, win, reqs
        gc.collect()
    found = limit_from(rows)
    print(f"calibrate: limit {found!r}", file=sys.stderr)
    if args.write_limit:
        path = harness.HERE / "cells" / f"{cell.name}.json"
        geometry = json.loads(path.read_text())
        name, limit = found or ("mean_logit_gap", None)
        geometry["limits"] = {"sample_requests": n, name: limit}
        path.write_text(json.dumps(geometry, indent=2) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
