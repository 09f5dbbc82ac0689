"""Host spans on the profiler's clock, around the host states of a round.

``span(name, **stats)`` returns a context manager that marks one stretch
of host work: waiting in the executor's poll, taking a request message,
publishing a round's results, waiting for a results slot, the heartbeat,
splicing an admission into the batched cache, blocking on the device for
a decode step's tokens, retiring a slot.  The manager's
``set_metadata(**stats)`` adds stats known only at the end (a take's
message count, whether a publish landed).

With no sink bound, ``span`` returns one shared no-op context: the cost
is a module-global test and a call.  ``use_profiler()`` binds
``jax.profiler.TraceAnnotation``, so each span is a TraceMe event on the
same clock as the device trace; XLA records those only while a profiler
session runs, so a process that binds the profiler and is never traced
pays one unrecorded TraceMe per span.  jax is imported only there:
``repro.obs`` and ``repro.core`` stay importable without it.

Every span name carries :data:`PREFIX`.
"""

from __future__ import annotations

__all__ = ["PREFIX", "span", "use_profiler", "use_sink"]

# the prefix of the host events that the repo's benchmark trace reader
# keeps (``SPAN_PREFIX`` in bench/trace.py); a span without it is dropped
PREFIX = "perfbench_"


class _Null:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **stats) -> None:
        pass


_NULL = _Null()
_sink = None


def span(name: str, **stats):
    """A context manager for host span ``PREFIX + name`` with ``stats``."""
    if _sink is None:
        return _NULL
    return _sink(PREFIX + name, **stats)


def use_sink(sink) -> None:
    """Bind ``sink(name, **stats)``, which returns a context manager with
    ``set_metadata``; ``None`` unbinds (spans become no-ops)."""
    global _sink
    _sink = sink


def use_profiler() -> None:
    """Emit every span as a ``jax.profiler.TraceAnnotation``."""
    from jax.profiler import TraceAnnotation

    use_sink(TraceAnnotation)
