"""Device milliseconds a decode step spends in the MoE MLPs: the self
time of the ``moe.route`` scope (router logits and top-k), of
``moe.experts`` (dispatch to the held experts, their SwiGLU and the
weighted combine) and of ``moe.shared`` (the shared expert), over the
step programs in the traced window.  None unless every step of the
window is a decode step, and where the program names none of these
scopes."""

from benchmarks.chip.scopes import ms_per_decode_step


def read(ctx):
    return ms_per_decode_step(ctx, ("moe.route", "moe.experts",
                                    "moe.shared"))
