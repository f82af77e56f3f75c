"""Host spans of the program, on the profiler's timeline.

`span(name, **args)` is a `jax.profiler.TraceAnnotation` named
"flight.<name>".  While a profiler session runs (`jax.profiler.trace`)
it records its start and end on the host thread, nested inside whatever
span encloses it there, with `args` (a request's `rid`, a chunk count) as
the event's stats; the profiler keeps the events in memory and writes
them out when the session stops.  With no session a span costs about a
microsecond, so spans stay in the code with no switch.  The prefix tells
the program's spans from other host events of the same trace.
"""
from __future__ import annotations

import jax

PREFIX = "flight."


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    return jax.profiler.TraceAnnotation(PREFIX + name, **args)
