"""Device milliseconds a decode step spends selecting keys: the self
time of the ``socket.select`` scope (the top-k over the scores), over
the step programs in the traced window.  None unless every step of the
window is a decode step."""

from benchmarks.chip.scopes import ms_per_decode_step


def read(ctx):
    return ms_per_decode_step(ctx, ("socket.select",))
