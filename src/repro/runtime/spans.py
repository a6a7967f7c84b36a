"""Host spans on the profiler's clock.

The program marks its host phases with ``jax.profiler.TraceAnnotation``
(steps with ``StepTraceAnnotation``), named ``repro.<layer>.<phase>``.
They are always compiled in and record only while a ``jax.profiler``
trace is active, so the trace is the only switch: with none open a span
costs about a microsecond.  Recorded spans land on the host planes of the
same ``.xplane.pb`` as the device's operations, on the same clock, so an
idle gap on the device can be named by the span the host was in.

A step marker carries its step number (the ``step_num`` stat); the spans
a step opens nest inside it on the same thread and share that number by
containment.
"""
from __future__ import annotations

from jax.profiler import StepTraceAnnotation, TraceAnnotation

PREFIX = "repro."


def span(name: str) -> TraceAnnotation:
    """Context manager: host span ``repro.<name>``."""
    return TraceAnnotation(PREFIX + name)


def step(name: str, step_num: int) -> StepTraceAnnotation:
    """Context manager: step marker ``repro.<name>`` with ``step_num``."""
    return StepTraceAnnotation(PREFIX + name, step_num=step_num)
