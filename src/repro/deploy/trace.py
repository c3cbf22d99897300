"""Named host spans on the profiler's clock.

A span is a ``jax.profiler.TraceAnnotation``: with no profiler session
active it costs about a microsecond, and while one traces (for example
``jax.profiler.start_trace``) the profiler keeps it and writes it into the
trace beside the device's events, so an idle gap on the device can be put
to what the host was doing.  Nothing is stored here.

The spans the serving stack opens:

* ``engine.step`` — one :meth:`~repro.deploy.engine.Engine.step`; inside it
  ``engine.admit`` (preemption and admission), ``session.prefill_chunks``
  and ``session.decode`` (the session's host preparation and dispatch),
  ``engine.device_wait`` (waiting for a step's logits), ``engine.fetch``
  (copying logits rows to the host), ``sampling`` (the sampling policy)
  and ``engine.stream`` (a request's ``on_token`` callback);
* ``engine.no_work`` — the async loop waiting for work;
* ``session.forward`` — one encoder call's host preparation and dispatch.

The device programs are named too: the session jits ``decode_step``,
``chunk_step``, ``prefill_step``, ``cow_copy`` and ``encoder_forward``,
and the executor runs each plan node under ``jax.named_scope(
"<kind>.<name>")``, so every device op's ``op_name`` names its plan node.
"""

from __future__ import annotations

from jax.profiler import TraceAnnotation

#: ``with span(name): ...`` opens the host span ``name`` (the annotation
#: itself, not a wrapper: no Python frame of its own under the profiler's
#: Python tracer)
span = TraceAnnotation
