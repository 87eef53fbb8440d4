"""Device milliseconds a decode step spends on sparse attention: the
self time of the ``socket.gather`` scope (the selected K/V rows read
from the pool) and of ``socket.attend`` (attention over them), over the
step programs in the traced window.  None unless every step of the
window is a decode step."""

from benchmarks.chip.scopes import ms_per_decode_step


def read(ctx):
    return ms_per_decode_step(ctx, ("socket.gather", "socket.attend"))
