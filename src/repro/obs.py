"""Host spans of the program's own layers, on the profiler's clock.

``span(name)`` is a ``jax.profiler.TraceAnnotation``: when no profiler
runs it costs under a microsecond; under ``jax.profiler.trace`` it
records the host interval in the same trace, and on the same clock, as
the device's ops, so an idle gap on the device can be put down to the
host step that covers it. Names start with ``repro.``; a layer opens one
span per dispatch, call or pump, never one per round or per flow.

    engine host loop  repro.fl.run > repro.fl.args, repro.fl.launch,
                      repro.fl.readback, repro.fl.records;
                      repro.fl.session_records
    serve engine      repro.serve.submit; repro.serve.take,
                      repro.serve.pad, repro.serve.score,
                      repro.serve.respond

The round program's device phases are ``jax.named_scope``s instead
(``fl_select`` … ``fl_eval`` in ``core/megastep.py``): op metadata that
the device trace carries with each op.
"""
from __future__ import annotations

import jax


def span(name: str) -> jax.profiler.TraceAnnotation:
    """A host span named ``name``, as a context manager."""
    return jax.profiler.TraceAnnotation(name)
