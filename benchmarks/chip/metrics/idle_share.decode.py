"""Share of the traced window in which no operation ran on the device,
in percent: 1 - (union of device-op intervals) / window."""


def read(ctx):
    red = ctx["trace"]
    if red is None or red["idle_share"] is None:
        return None
    return 100.0 * red["idle_share"]
