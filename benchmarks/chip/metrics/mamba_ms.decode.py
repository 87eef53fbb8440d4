"""Device milliseconds a decode step spends in the Mamba-2 layers: the
self time of the ``mamba.proj`` scope (in_proj and out_proj), of
``mamba.scan`` (conv, recurrence, gated norm) and of ``mamba.state``
(every slot's state rows kept or taken after the step), over the step
programs in the traced window.  None unless every step of the window is
a decode step, and where the program names none of these scopes."""

from benchmarks.chip.scopes import ms_per_decode_step


def read(ctx):
    return ms_per_decode_step(ctx, ("mamba.proj", "mamba.scan",
                                    "mamba.state"))
