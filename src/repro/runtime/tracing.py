"""Host spans on the profiler's clock.

``span(name, **counts)`` is a ``jax.profiler.TraceAnnotation`` named
``repro.<name>``. While a profiler session is active (``jax.profiler.
start_trace``) each span is written, with its counts, into the same
trace as the device's operations and on the same clock; with no session
it records nothing and costs about a microsecond. Spans opened inside
one another nest.

Rules for every span in the program:

- a span, count or scope exists only where something reads it: a
  per-layer metric of the benchmark, or the naming of idle gaps and
  device time by scope (``bench/lib/program_trace.py``);
- there is no buffer, exporter, flag or environment variable: the
  profiler session is the only switch;
- counts are host integers the code already holds;
- a span never adds ``block_until_ready``, ``np.asarray``, ``float()``
  or ``int()`` of a device value: it brackets the host work that is
  there, and a sync the code already makes gets a span of its own.

Device work is named with ``jax.named_scope`` where it is traced, which
changes only the compiled program's ``op_name`` metadata. Compiles need
no span: JAX writes its own ``backend_compile_and_load`` event.
"""

from __future__ import annotations

import jax

PREFIX = "repro."


def span(name: str, **counts: int) -> jax.profiler.TraceAnnotation:
    """A host span ``repro.<name>`` with integer ``counts``; use it as a
    context manager. Counts known only inside the span are added with
    ``set_metadata(**counts)`` on the object ``with`` gives."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **counts)
