"""Program spans: the serving thread's layer boundaries on the profiler's
clock.

Each boundary opens ``span("repro.<layer>.<step>", **meta)``, a
``jax.profiler.TraceAnnotation``. With no profiler session running it
costs about a microsecond to enter and leave and records nothing; under
``jax.profiler.trace`` / ``start_trace`` it is written into the
profiler's host plane, on the same clock as the device's operations, so
a gap on the device reads as the host work around it. Spans of one
thread nest, so a span's self time is its length less its children's.

    repro.gateway.submit     submit / submit_many / flush, pane formation
    repro.gateway.pane       one pane launched (meta: pane id, rows,
                             overlapped); requests link to it by
                             RequestTelemetry.pane_id
    repro.gateway.retire     one pane read back and answered (meta: pane
                             id); in a drain of several panes it follows
                             the next pane's launch
    repro.gateway.readback   the pane's scores read back to the host
    repro.gateway.respond    telemetry and Response of each row
    repro.feature.observe    observe / observe_many
    repro.feature.histories  batch-history lookup and tokenisation
    repro.feature.suffixes   fresh-suffix lookup and tokenisation
    repro.feature.tokens     padding token lists to a pane (pad_tokens)
    repro.pool.gather        slot operands placed, gather launched
    repro.pool.scatter       rows placed, scatter launched
    repro.engine.prefill     operands placed, program launched; each
    repro.engine.inject      engine span ends when the call returns a
    repro.engine.finalize    device array, before the device has run it
    repro.engine.slate
    repro.engine.readback    the slate read back to the host
"""
from __future__ import annotations

import jax


def span(name: str, **meta) -> jax.profiler.TraceAnnotation:
    """A context manager that records ``name`` (with ``meta``) while a
    profiler session runs, and nothing otherwise."""
    return jax.profiler.TraceAnnotation(name, **meta)
