"""Device milliseconds a decode step spends scoring keys: the self time
of the ``socket.score`` scope (the packed bits read through the block
table and the scoring kernel) and of the query hash inside it
(``socket.hash``), over the step programs in the traced window.  None
unless every step of the window is a decode step."""

from benchmarks.chip.scopes import ms_per_decode_step


def read(ctx):
    return ms_per_decode_step(ctx, ("socket.score", "socket.hash"))
