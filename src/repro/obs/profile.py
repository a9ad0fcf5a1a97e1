"""Optional ``jax.profiler`` hooks: line device profiles up with spans.

Host-side spans (:mod:`repro.obs.trace`) stop at the jit boundary — the
device timeline in a ``jax.profiler`` trace shows XLA op names, not
"which phase of the program was this".  While annotations are enabled,
every live :class:`~repro.obs.trace.Span` opens a
``jax.profiler.TraceAnnotation`` of its own name (see
:meth:`repro.obs.trace.Span.__enter__`), so the trace's host plane holds
the program's span tree on the same clock as the device planes.

Annotations are off by default (they cost a C++ call even when no
profiler session is active) and enabled process-wide via
:func:`enable` or the ``REPRO_JAX_PROFILE`` env var.  When off,
:func:`annotate` returns a shared no-op context manager; when jax's
profiler is unavailable the hooks silently stay off — this module never
makes jax a hard import requirement for the tracer.
"""

from __future__ import annotations

import os

__all__ = ["annotate", "enable", "disable", "enabled"]


class _NullAnnotation:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _NullAnnotation()
_enabled = False
_trace_annotation = None     # resolved lazily on first enable()


def _resolve():
    global _trace_annotation
    if _trace_annotation is None:
        try:
            from jax.profiler import TraceAnnotation
            _trace_annotation = TraceAnnotation
        except Exception:            # pragma: no cover - jax always present
            _trace_annotation = False
    return _trace_annotation


def enable() -> bool:
    """Turn profiler annotations on; returns whether jax's profiler is
    actually available."""
    global _enabled
    _enabled = bool(_resolve())
    return _enabled


def disable() -> None:
    global _enabled
    _enabled = False


def enabled() -> bool:
    return _enabled


def annotate(name: str, **stats):
    """A context manager marking ``name`` on the profile's host timeline
    when enabled, or a shared no-op otherwise.  ``stats`` become the
    event's stats in the trace.

    Usage::

        with annotate("probe", step=3):
            out = jitted_fn(batch)
    """
    if _enabled and _trace_annotation:
        return _trace_annotation(name, **stats)
    return _NULL


if os.environ.get("REPRO_JAX_PROFILE", "").strip() not in ("", "0"):
    enable()
