"""The window's algorithmic operations (weights, SOCKET hashing and
scoring, attention over the selected rows; ``flops.py``) over the
window's length and the chip's peak, in percent."""


def read(ctx):
    total = sum(s["flops"] for s in ctx["steps"])
    if not total or ctx["window_s"] <= 0:
        return None
    return 100.0 * total / ctx["window_s"] / ctx["peak"]["flops_bf16"]
