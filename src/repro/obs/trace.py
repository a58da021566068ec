"""Host spans on the profiler's clock.

The run driver and the serving tier mark the boundaries of their host
work with :func:`span`, a ``jax.profiler.TraceAnnotation``. While a
profiler trace runs (``jax.profiler.start_trace``), each span lands in
it on the host thread that opened it, on the same clock as the device's
ops, so an idle gap on the device can be put down to what the host was
doing then. With no trace running a span records nothing and keeps
nothing; entering one costs about a microsecond.

The spans (one per set-up, call or event, never per slot or per agent):

================================  ==========================================
``repro.engine.place_tables``     an ``AsyncEngine``'s set-up: its static
                                  tables stored in the scan's layouts
``repro.run``                     one ``run()`` call of either engine
``repro.run.advance``             the dispatch of one scan chunk
``repro.run.<event>``             a periodic callback: ``publish``,
                                  ``record``, ``drain``, ``checkpoint``
``repro.run.topology``            a graph refresh or an admission
``repro.run.result``              building ``SimResult`` (its host pulls)
``repro.serve.publish``           ``ServeHandle.publish``
``repro.serve.publish.sync``      its wait for the slot counter
``repro.serve.predict``           ``ServeHandle.predict`` / ``rows``
``repro.serve.route``             id checks, routing, host-to-device copies
``repro.serve.score``             the dispatch of the serving program
``repro.serve.fetch``             the wait for its result on the host
================================  ==========================================

Inside the jitted super-tick the phases are ``jax.named_scope("obs.*")``
scopes instead; they reach a device trace through each op's HLO
metadata. JAX's persistent compilation cache leaves that metadata out of
its key by default, so a program that differs from a cached one only in
its scopes would run the cached executable and be profiled under the
other's scopes. Importing this module keys the cache on the metadata
too.
"""

from __future__ import annotations

import jax

jax.config.update("jax_compilation_cache_include_metadata_in_key", True)


def span(name: str) -> jax.profiler.TraceAnnotation:
    """A host span named ``name``: use it as a context manager."""
    return jax.profiler.TraceAnnotation(name)
