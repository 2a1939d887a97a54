"""Engine phase spans: one context manager per host phase of a step.

Every span enters `jax.profiler.TraceAnnotation("repro.<name>",
step=<step id>)`, so a profile taken with `jax.profiler.trace` (or captured
from a `jax.profiler.start_server` port) shows what the engine's host
thread was doing on the same clock as the device's ops.  The annotation is
always on: with no profiler recording it costs about a microsecond and
no host sync.  When a `Telemetry` is attached the span also feeds its
phase histogram and Chrome tracer under `phase` (the telemetry's older
name for the phase, where it has one), with `args` as the trace event's
arguments.  The spans and what each covers are listed in
docs/observability.md.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

PREFIX = "repro."


class Span:
    """`with Span("pack", step, telemetry) as sp: ...`; `sp.t0` is the
    telemetry clock at entry (0.0 without telemetry) and `sp.args` may
    be filled in before exit."""

    __slots__ = ("_ann", "_tel", "_phase", "args", "t0")

    def __init__(self, name: str, step: int, telemetry=None,
                 phase: str | None = None, **args):
        self._ann = TraceAnnotation(PREFIX + name, step=step)
        self._tel = telemetry
        self._phase = phase or name
        self.args = args
        self.t0 = 0.0

    def __enter__(self) -> "Span":
        if self._tel is not None:
            self.t0 = self._tel.clock.now()
        self._ann.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        self._ann.__exit__(*exc)
        if self._tel is not None:
            self._tel.record_phase(self._phase, self.t0,
                                   self._tel.clock.now(), **self.args)
