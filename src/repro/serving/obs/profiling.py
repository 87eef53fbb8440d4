"""Profiler hooks: ``jax.profiler`` trace capture around K engine steps.

``--profile-dir`` wires a :class:`Profiler` into the engine loop: the
capture starts at iteration ``start_step``, runs for ``steps``
iterations, and stops (also force-stopped at run end if the run is
shorter).  Inside the window every phase of an engine iteration runs
under a ``jax.profiler.TraceAnnotation`` named from :data:`HOST_SPANS`,
so the host timeline in TensorBoard / Perfetto says which host code each
device idle gap waited on, and the device programs are matched to the
``decode``/``mixed`` span that dispatched them.  ``engine.run`` spans the
whole window, so the few microseconds of loop between two phases are
named too.  Python GC passes inside the window show as ``host.gc`` (a
``gc.callbacks`` hook, registered only while the window is open).

Start/stop are mirrored into the event trace (``profile_start`` /
``profile_stop``); ``profile_start.clock_ns`` is the tracer's epoch on
the wall clock (``time.time_ns``) the profiler stamps host events with,
so ``ts * 1e9 + clock_ns`` places any event of the JSONL trace on the
device trace.  Outside the window, and in an engine without a profiler,
each span is one shared null context: profiling allocates nothing for
un-profiled steps.
"""

from __future__ import annotations

import contextlib
import gc

import jax

__all__ = ["HOST_SPANS", "NULL_SPAN", "Profiler", "null_span"]

# Every host span the engine emits: the profile window's ``engine.run``,
# then, in the order one iteration runs them, its phases.
# ``decode``/``mixed`` wrap the jitted dispatch (the device program of
# each step is matched to them by name); the others name the host work
# between dispatches; ``host.gc`` is a Python GC pass wherever it lands.
HOST_SPANS = ("engine.run", "engine.schedule", "engine.tables",
              "engine.probe", "decode", "mixed", "engine.sync",
              "engine.emit", "host.gc")

NULL_SPAN = contextlib.nullcontext()


def null_span(name: str):
    """The span of an engine without a profiler: always :data:`NULL_SPAN`."""
    return NULL_SPAN


class Profiler:
    """Window-of-K-steps ``jax.profiler`` capture for the engine loop."""

    def __init__(self, profile_dir: str, steps: int = 20,
                 start_step: int = 0):
        assert steps > 0, steps
        self.profile_dir = profile_dir
        self.steps = int(steps)
        self.start_step = int(start_step)
        self.active = False
        self._done = False                  # one window per run
        self._run_span = None
        self._gc_span = None

    def maybe_start(self, iteration: int, tracer=None) -> None:
        if self._done or self.active or iteration < self.start_step:
            return
        # the host spans account for the engine's host time, so Python
        # calls are not traced one by one (a cost on every call)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.profile_dir, profiler_options=options)
        self.active = True
        self._run_span = jax.profiler.TraceAnnotation("engine.run")
        self._run_span.__enter__()
        gc.callbacks.append(self._on_gc)
        self._stop_at = iteration + self.steps
        if tracer is not None:
            tracer.emit("profile_start", dir=self.profile_dir,
                        steps=self.steps, clock_ns=tracer.clock_ns())

    def maybe_stop(self, iteration: int, tracer=None) -> None:
        """Stop after the window's last step has dispatched (called with
        the next iteration number)."""
        if self.active and iteration >= self._stop_at:
            self.stop(tracer)

    def stop(self, tracer=None) -> None:
        """Force-stop (run end); idempotent."""
        if not self.active:
            return
        gc.callbacks.remove(self._on_gc)
        self._run_span.__exit__(None, None, None)
        self._run_span = None
        jax.profiler.stop_trace()
        self.active = False
        self._done = True
        if tracer is not None:
            tracer.emit("profile_stop", dir=self.profile_dir)

    def annotate(self, name: str):
        """Named trace annotation inside the window, null context outside."""
        if self.active:
            return jax.profiler.TraceAnnotation(name)
        return NULL_SPAN

    def _on_gc(self, phase: str, info: dict) -> None:
        # a collection holds the GIL from "start" to "stop", so one slot
        # holds the open span
        if phase == "start":
            self._gc_span = jax.profiler.TraceAnnotation("host.gc")
            self._gc_span.__enter__()
        elif self._gc_span is not None:
            self._gc_span.__exit__(None, None, None)
            self._gc_span = None
