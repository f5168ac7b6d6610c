"""Profiler spans of the pricing engine.

``span(name, **stats)`` is ``jax.profiler.TraceAnnotation(name, **stats)``
once JAX is imported, and a null context before, so the numpy backend
prices without importing JAX.  Stats are host numbers and strings only:
no span reads a device value, so tracing adds no sync.  With no profiler
session recording, a span costs well under a microsecond.

The spans, each named ``repro.price*``, and where they open:

* ``repro.price`` — one :func:`~repro.core.price` call (``backend``,
  ``bundles``, ``scenarios``);
* ``repro.price.pack`` — compiling a bundle list and packing it into one
  super-bundle, or finding the one an earlier call packed from bundles of
  the same content (``calls``; ``reused``: 1 when it was found);
* ``repro.price.run`` — the jitted executor's call: enqueue, and on a miss
  of the bundle's jit cache also trace, lower and compile or cache read
  (``jit_miss``);
* ``repro.price.fetch`` — the wait for the device, the copy of its
  outputs to the host and their one float64 pass into each bundle's
  matrices (``host_mb``: the float64 megabytes written); the streaming
  executor's copy of a chunk's reductions;
* ``repro.price.split`` — building each bundle's result from its
  matrices;
* ``repro.price.merge`` — the streaming executor's host merge of one
  chunk's candidates (``rows``: the chunk's scenarios);
* ``repro.price.exact`` — its exact re-pricing of the survivors (``rows``).
"""
from __future__ import annotations

import contextlib
import sys


def span(name: str, **stats):
    """A profiler span ``name`` carrying ``stats``, or a null context where
    JAX is not imported.  Stats known only inside the span are added with
    ``set_metadata(**stats)`` on what the ``with`` binds, where that is
    not ``None``."""
    jax = sys.modules.get("jax")
    if jax is None:
        return contextlib.nullcontext()
    return jax.profiler.TraceAnnotation(name, **stats)
