"""Named spans of the program's phases, for a profiler's timeline.

TALP's own scopes (regions, the Offload/MPI host states, the overhead
sections) and the drivers' loop phases mark where the host is. This
module hands each of them, as a named span, to one process-global sink:
with ``jax.profiler.TraceAnnotation`` as the sink they land in the
profiler's trace, on the same clock as the device's operations, so every
idle instant of the device can be put down to what the host was doing.

Span names:

  * ``talp.region.<name>`` — a monitor region window, with ``step=`` the
    0-based index of that window (the step-series row it becomes);
  * ``talp.offload`` / ``talp.mpi`` — the host-state scopes;
  * ``talp.capture.<section>`` — the monitor's own work, one per
    :mod:`.overhead` section;
  * ``serve.*`` / ``train.*`` — the drivers' loop phases.

Arguments are host integers only; no span reads a device value. With no
sink installed a span costs one global load and a ``None`` check. The
module imports nothing beyond the standard library: the drivers install
a JAX-backed sink, the core stays free of JAX.
"""

from __future__ import annotations

from contextlib import nullcontext
from typing import Any, Callable, ContextManager, Optional

__all__ = ["install", "current", "begin", "end", "span"]

#: ``sink(name, **args)`` makes one span as a context manager.
Sink = Callable[..., ContextManager]

_sink: Optional[Sink] = None
_NO_SPAN = nullcontext()


def install(sink: Optional[Sink]) -> Optional[Sink]:
    """Install ``sink`` process-globally (``jax.profiler.TraceAnnotation``
    in the drivers); returns the one it replaces."""
    global _sink
    prev = _sink
    _sink = sink
    return prev


def current() -> Optional[Sink]:
    return _sink


def begin(name: str, **args: int) -> Any:
    """Open a span; returns the token :func:`end` closes (``None`` when no
    sink is installed). Tokens, not a stack: spans of two monitors may
    close out of order, and a span outlives a change of sink."""
    sink = _sink
    if sink is None:
        return None
    cm = sink(name, **args)
    cm.__enter__()
    return cm


def end(token: Any) -> None:
    if token is not None:
        token.__exit__(None, None, None)


def span(name: str, **args: int) -> ContextManager:
    """``with span("serve.fetch"):`` — one span around a block."""
    sink = _sink
    return _NO_SPAN if sink is None else sink(name, **args)
