"""Host turnaround of a decode step, in milliseconds: the mean ``host_s``
of the window's decode step records, from one step's token reaching the
host to the end of the next step's dispatch, the time in which the
device has nothing queued.  None where the window holds a mixed step or
the step records carry no ``host_s``."""


def read(ctx):
    steps = ctx["step_events"]
    if not steps or any(e["kind"] != "decode" for e in steps):
        return None
    host = [e["host_s"] for e in steps if e.get("host_s") is not None]
    return 1e3 * sum(host) / len(host) if host else None
