"""One span, two sinks (DESIGN.md §11.1).

Every host-side span of the serving stack goes through :func:`span`: a
``jax.profiler.TraceAnnotation``, which lands on the host timeline of a
``jax.profiler`` capture on the device trace's clock, and, when a tracer is
attached, a :class:`repro.obs.trace.Tracer` span of the same name. One span
has one name in both sinks, so a ring trace and a device profile of the same
serve line up by name. It lives here and not in ``repro.obs``, which may not
import jax (``lint/obs-host-only``).
"""

from __future__ import annotations

import contextlib

import jax

from repro.obs.trace import NULL_SPAN


@contextlib.contextmanager
def span(tracer, name: str, *, lane: str, **args):
    """Span ``name`` around host work between dispatches, never inside
    jitted code. ``tracer`` is a ``Tracer`` or ``None``; its span goes on
    ``lane`` (also its category) with ``args``, reads the tracer's own clock,
    and is a shared no-op when the tracer is absent or disabled, so no clock
    is read. Yields the ring handle: ``annotate()`` adds args found mid-span.
    A ``TraceAnnotation`` with no profiler session running is a cheap no-op."""
    ring = (
        NULL_SPAN if tracer is None
        else tracer.span(name, cat=lane, lane=lane, **args)
    )
    with ring, jax.profiler.TraceAnnotation(name):
        yield ring
