"""Mean number of requests decoding per engine step in the window, from
the engine's own step records (``occupancy`` of each ``step`` event)."""


def read(ctx):
    occ = [e["occupancy"] for e in ctx["step_events"]]
    return sum(occ) / len(occ) if occ else None
