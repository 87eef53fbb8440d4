"""Device milliseconds a decode step spends outside every named scope
(a CPU trace's ops carry no op-name path, so all of its time is here)."""

from benchmarks.chip.scopes import ms_per_decode_step


def read(ctx):
    return ms_per_decode_step(ctx, ("unscoped",))
