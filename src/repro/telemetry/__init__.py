"""Telemetry plane: metrics registry, span tracing, kernel profiling.

The seventh plane. One :class:`TelemetrySession` (registry + tracer)
is installed process-wide for the duration of a run; instrumentation
sites across the other six planes call the module-level helpers below,
which are no-ops while no session is active.

The load-bearing contract (mirrors the trace plane's):

* **Off is free.** Telemetry defaults to off; every hook is then one
  global load + ``None`` check and *no* telemetry object is ever
  constructed — runs reproduce the committed golden traces
  bit-identically (``tests/test_telemetry.py`` pins this).
* **On never perturbs exact streams.** Spans and counters observe;
  they never feed back into sampling, scoring, decisions, or byte
  accounting — telemetry-on runs keep the same
  ``Trace.exact_digest()``. Only wall-clock (already excluded from
  exact digests) can move, within the CI-gated budget
  (``benchmarks/telemetry_smoke.py``).

Usage::

    trainer = DistributedTrainer(parts, telemetry=True)
    result = trainer.run()
    result.telemetry["spans"]["by_plane"]      # seconds per plane
    trainer.last_telemetry.write_jsonl("run.jsonl")
    # python -m repro.telemetry summary run.jsonl

See ``docs/OBSERVABILITY.md`` for the full reference.
"""

from __future__ import annotations

import functools
from contextlib import contextmanager

from .calibrate import (
    Calibration,
    calibrate_from_session,
    calibrate_from_trace,
    fit_alpha_bw,
)
from .provenance import provenance
from .registry import Counter, Gauge, Histogram, MetricsRegistry
from .session import TelemetrySession
from .spans import Span, SpanTracer

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "Span",
    "SpanTracer",
    "TelemetrySession",
    "Calibration",
    "fit_alpha_bw",
    "calibrate_from_trace",
    "calibrate_from_session",
    "provenance",
    "current",
    "enabled",
    "activate",
    "deactivate",
    "active",
    "span",
    "begin",
    "end",
    "count",
    "spanned",
    "profiled",
]

_SESSION: TelemetrySession | None = None


class _NullSpan:
    """Shared do-nothing span for telemetry-off code paths.

    Deliberately *not* ``__slots__``-restricted: instrumented code sets
    attributes on the span it holds (``sp.nbytes = ...``) and must not
    care whether telemetry is live.
    """

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False


_NULL_SPAN = _NullSpan()


def current() -> TelemetrySession | None:
    return _SESSION


def enabled() -> bool:
    return _SESSION is not None


def activate(session: TelemetrySession) -> TelemetrySession:
    global _SESSION
    if _SESSION is not None:
        raise RuntimeError("a telemetry session is already active")
    _SESSION = session
    return session


def deactivate() -> None:
    global _SESSION
    _SESSION = None


@contextmanager
def active(session: TelemetrySession):
    """Install ``session`` as the process-wide session for the block."""
    activate(session)
    try:
        yield session
    finally:
        deactivate()


# -- cheap instrumentation helpers (the only API call sites use) ------- #
def span(name: str, pe: int = -1, plane: str = "", nbytes: int = 0):
    s = _SESSION
    if s is None:
        return _NULL_SPAN
    return s.tracer.span(name, pe=pe, plane=plane, nbytes=nbytes)


def begin(name: str, pe: int = -1, plane: str = ""):
    """Open a span without a ``with`` block; pair with :func:`end`.

    Returns ``None`` when telemetry is off — ``end(None)`` is a no-op,
    so loop bodies stay un-indented at zero cost.
    """
    s = _SESSION
    if s is None:
        return None
    return s.tracer.begin(name, pe=pe, plane=plane)


def end(token) -> None:
    if token is not None:
        token.__exit__(None, None, None)


def count(name: str, value=1, shape=None) -> None:
    s = _SESSION
    if s is None:
        return
    s.registry.counter(name, shape=shape).add(value)


def spanned(name: str, plane: str = ""):
    """Method/function decorator: run the call under a span when on.

    Off-path cost is one global load + ``None`` check per call — no
    span object, no context manager, no tracer touch.
    """

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            s = _SESSION
            if s is None:
                return fn(*args, **kwargs)
            with s.tracer.span(name, plane=plane):
                return fn(*args, **kwargs)

        return wrapper

    return deco


def profiled(name: str):
    """Kernel-dispatcher decorator: block-until-ready timing when on.

    With no active session (or ``profile_kernels=False``) the wrapper
    is a direct call — no timing, no blocking, no extra sync points, so
    the device pipeline's async launch overlap is untouched by default.
    """

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            s = _SESSION
            if s is None or not s.profile_kernels:
                return fn(*args, **kwargs)
            return s.profile_call(name, fn, *args, **kwargs)

        return wrapper

    return deco
