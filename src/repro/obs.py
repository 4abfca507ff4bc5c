"""Spans of the serving path, on the profiler's clock.

``span(name, **args)`` returns a ``jax.profiler.TraceAnnotation`` named
``repro.<name>`` while spans are on, and a shared no-op context while
they are off (the default). A profiler trace then holds the program's
layer boundaries on the same clock as the device's operations, so an
idle gap on the device can be named by the program phase behind it.
The annotations stay in the profiler's memory and are written when the
trace stops.

With spans off a span costs one module-level boolean check: no
annotation is built and nothing is formatted. Pass cheap ints as
``args``. A value known only at the end of the span goes in through
``set_metadata`` on what ``span`` returned, which is a no-op when off;
where that value costs work, guard it with ``if sp:``.

Spans are turned on by code (``enable(True)``), by whoever also starts
the profiler; nothing in the environment controls them.
"""
from __future__ import annotations

from jax import profiler as _profiler

PREFIX = "repro."

_on = False


class _Off:
    """The span used while spans are off: enters, exits and takes
    metadata, doing nothing. It is falsy, so ``if sp:`` guards metadata
    that would cost something to compute."""

    __slots__ = ()

    def __bool__(self) -> bool:
        return False

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **args) -> None:
        pass


_OFF = _Off()


def enable(on: bool = True) -> None:
    """Turn the program's spans on or off."""
    global _on
    _on = bool(on)


def span(name: str, **args):
    """A span named ``repro.<name>`` with ``args`` as its metadata when
    spans are on; the shared no-op span otherwise."""
    if not _on:
        return _OFF
    return _profiler.TraceAnnotation(PREFIX + name, **args)
