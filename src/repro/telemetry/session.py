"""TelemetrySession: one run's registry + tracer + kernel profiler.

A session owns a :class:`MetricsRegistry` and a :class:`SpanTracer`
and is installed as the process-wide active session for the duration
of one ``DistributedTrainer.run()`` (see :func:`repro.telemetry.active`).
Instrumentation sites never hold a session reference — they ask the
module-level helpers, which are no-ops when nothing is active. That
indirection is the zero-overhead-off contract: with no session, every
hook is one global load and a ``None`` check.

Kernel profiling (``profile_call``) wraps a dispatcher call with
``jax.block_until_ready`` timing — the block is what makes the number
mean "kernel finished", not "dispatch returned" — inside a
``jax.profiler.TraceAnnotation`` named ``kernel.<name>``, as its
counters are, so the call shows up in a profiler capture beside the
tracer's spans (which annotate under their own names).
"""

from __future__ import annotations

import time

from .registry import MetricsRegistry
from .spans import SpanTracer

__all__ = ["TelemetrySession"]


class TelemetrySession:
    def __init__(
        self,
        label: str = "run",
        profile_kernels: bool = True,
    ):
        self.label = label
        self.profile_kernels = profile_kernels
        self.registry = MetricsRegistry()
        self.tracer = SpanTracer()
        self.meta: dict = {}

    # -- kernel profiling ---------------------------------------------- #
    def profile_call(self, name: str, fn, *args, **kwargs):
        """Call ``fn`` with block-until-ready timing under ``name``."""
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"kernel.{name}"):
            out = fn(*args, **kwargs)
            jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        self.registry.counter(f"kernel.{name}.calls").add(1)
        self.registry.histogram(f"kernel.{name}.seconds").observe(dt)
        return out

    # -- aggregation --------------------------------------------------- #
    def summary(self) -> dict:
        """Flat JSON-safe summary merged into RunResult / sweep rows."""
        return {
            "label": self.label,
            "spans": self.tracer.summary(),
            "metrics": self.registry.summary(),
            "meta": dict(self.meta),
        }

    def brief(self) -> dict:
        """Compact per-cell summary for sweep rows: seconds by plane
        plus counter totals (no per-element arrays, no histograms)."""
        counters = {
            name: self.registry[name].total
            for name in self.registry.names()
            if self.registry[name].kind == "counter"
        }
        return {
            "wall_s": self.tracer.total_s(),
            "span_count": len(self.tracer.spans),
            "by_plane": dict(sorted(self.tracer.by_plane().items())),
            "counters": counters,
        }

    # -- export (delegates; see export.py) ----------------------------- #
    def write_jsonl(self, path) -> None:
        from .export import write_jsonl

        write_jsonl(self, path)

    def write_chrome_trace(self, path) -> None:
        from .export import write_chrome_trace

        write_chrome_trace(self, path)
