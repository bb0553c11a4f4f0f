"""Program spans on the profiler's clock (DESIGN.md section 13.3).

Every span the program opens is a `jax.profiler.TraceAnnotation`, so it
lands in the same trace as the device operations it dispatches and
waits on: one clock, one file, and a device idle gap can be named by
the host span that covers it. Spans are recorded exactly while a
profiler session is active in the process — `--trace-out DIR` starts
one (`launch.common.setup_obs`), and so does any caller of
`jax.profiler.start_trace` / `jax.profiler.trace` (the benchmark's
traced window). There is no switch of its own.

Cost contract: with no profiler session, `span(...)` is one check (the
profiler's own `is_enabled`) and returns the shared null span — no
object is built. Span arguments are attached after the fact, and only
while tracing:

    with obs.span("path.point") as sp:
        ...
        if sp is not None:          # tracing: attach the arguments
            sp.set_metadata(i=i, c=c)

The null span enters as None, so the arguments are never built while
tracing is off. Span names carry their layer as a prefix (`engine.`,
`serve.`, `path.`, `kernels.`, `autotune.`); host events land on the
line of the thread that opened them.
"""
from __future__ import annotations

from typing import Optional

from jax.profiler import TraceAnnotation

_tracing = TraceAnnotation.is_enabled


class _NullSpan:
    """Shared disabled-path context manager: enters as None."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


def enabled() -> bool:
    """True while a profiler session records host annotations."""
    return _tracing()


def span(name: str):
    """A `TraceAnnotation` named `name` while tracing, else the null span."""
    if not _tracing():
        return _NULL_SPAN
    return TraceAnnotation(name)


def instant(name: str, args: Optional[dict] = None) -> None:
    """A zero-length span: a marker on the timeline (guard trips and other
    rare events, so `args` may be built by the caller)."""
    if _tracing():
        with TraceAnnotation(name, **(args or {})):
            pass
