"""Observability layer: structured tracing for rounds, sweeps, serving.

Near-zero overhead when disabled by construction: every instrumented
call site holds a tracer that is either a real :class:`~repro.obs.trace.
Tracer` (JSON-lines span/counter/event emission) or the shared
:data:`~repro.obs.trace.NULL_TRACER` whose methods write nothing and
whose ``span()`` is a bare ``jax.profiler.TraceAnnotation``: both
tracers' spans reach a profiler trace being recorded, on the device's
clock.  Tracing is host-side only — it never touches RNG streams,
device buffers, or numerics — so ``obs`` disabled (the default) is a
bitwise no-op on every engine x backend x precision leg, and *enabled*
changes timing visibility, not trajectories (asserted by
tests/test_obs.py).

Enable per-run via ``ExperimentSpec(obs=ObsSpec(enabled=True))``, the
CLI ``--trace`` flag, or ``$FEDPHD_OBS=1`` (resolution contract:
``explicit > env > off``, owned by repro.experiment.resolve).

Trace schema: see repro.obs.trace (one JSON object per line, stable
golden keys) and README "Observability".
"""
from repro.obs.compile_tracker import CompileTracker, cache_size
from repro.obs.metrics import read_trace, summarize_trace
from repro.obs.spec import ObsSpec
from repro.obs.trace import (NULL_TRACER, SCHEMA_VERSION, NullTracer,
                             Tracer, make_tracer)

__all__ = ["CompileTracker", "cache_size", "read_trace", "summarize_trace",
           "ObsSpec", "NULL_TRACER", "SCHEMA_VERSION", "NullTracer",
           "Tracer", "make_tracer"]
