"""Host spans of the program, in the profiler's own trace.

``span(name, **meta)`` is a ``jax.profiler.TraceAnnotation`` named
``repro.<name>``.  It records only while a ``jax.profiler`` trace runs, on
the same clock as the device's events; with no profiler running, entering
and leaving one costs about a microsecond.  Spans go around phases of a
tick or a tuning call, never per lane or per token, and never inside
traced (jitted) code, where ``jax.named_scope`` names the device ops.

Spans of the serving engine (``serving/engine.py``), nested so that the
children tile their parent:

* ``engine.schedule``: ``engine.admit``, then one ``engine.prefill_chunk``
  per chunk (``rid``, ``start``, ``end``);
* ``engine.dispatch``: ``engine.dispatch.inputs`` (page capacity, token and
  position arrays, page table to the device), ``engine.dispatch.call``
  (the decode step's enqueue, ``lanes``; on a paged cache also
  ``kv_tile``, the decode kernel's tokens per grid step,
  ``kv_blocks``, the blocks the decoding lanes' walks take in all, and
  ``kv_bytes``, the bytes of live K and V the decode kernel reads: the
  decoding lanes' contexts times the pool's bytes per token, which is
  layers x 2 x KV heads x head dim x cache itemsize);
* ``engine.emit``: ``engine.emit.wait`` (the step's device work),
  ``engine.emit.fetch`` (logits to the host), ``engine.emit.sample``,
  ``engine.emit.finish`` (per-lane bookkeeping).

And of run-time auto-tuning (``core/runtime.py``): ``at.candidate``
(``region``, ``idx``), one timed call of a candidate.
"""
import jax

PREFIX = "repro."


def span(name: str, **meta) -> jax.profiler.TraceAnnotation:
    return jax.profiler.TraceAnnotation(PREFIX + name, **meta)
