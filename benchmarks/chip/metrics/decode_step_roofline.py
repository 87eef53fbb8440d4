"""Roofline share of the decode-only step, in percent: the least time
the chip could take for the window's decode steps (the larger of their
operations over peak FLOP/s and their bytes over HBM bandwidth, step by
step, from ``flops.py``) over the device time the trace gives those
steps, each taken as the longest program that step dispatched."""


def read(ctx):
    red = ctx["trace"]
    dev = (red or {}).get("step_device_s", {}).get("decode")
    steps = [s for s in ctx["steps"] if s["kind"] == "decode"]
    if not dev or not steps:
        return None
    pk = ctx["peak"]
    bound = sum(max(s["flops"] / pk["flops_bf16"],
                    s["bytes"] / pk["hbm_bytes_per_s"]) for s in steps)
    return 100.0 * (bound / len(steps)) / (sum(dev) / len(dev))
