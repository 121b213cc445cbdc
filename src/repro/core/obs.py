"""Names the round engine gives its work in a profiler trace.

Two kinds, both free when nobody traces:

- ``scope(name)``: a device scope, ``jax.named_scope``. It acts while a
  function is traced and only prefixes the ``op_name`` metadata of the HLO
  instructions made inside it; the arithmetic is the same with or without
  it. Scopes nest, and the innermost name of ``SCOPES`` in an instruction's
  ``op_name`` path is its layer.
- ``span(name)``: a host span, ``jax.profiler.TraceAnnotation``. It is
  written into the trace of an active profiler session, on the thread that
  enters it and on the device's clock; with no session it records nothing.

Take a trace with ``jax.profiler.start_trace(dir)`` ... ``stop_trace()``
around some rounds (DESIGN.md §6.7).
"""
from __future__ import annotations

import jax

#: device scopes, in the order a round runs them
SCOPES = ("client.step", "uplink.encode", "uplink.reduce", "server.step")

#: host spans on the trainer's thread
SPANS = ("round.dispatch", "feed.wait", "slab.place", "slab.call",
         "finalize.call", "bucket.call", "round.absorb", "loss.sync")


def scope(name: str):
    """A device scope: ``with scope("client.step"): ...`` inside a traced
    function."""
    _known(name, SCOPES)
    return jax.named_scope(name)


def span(name: str):
    """A host span: ``with span("slab.call"): ...`` around host code."""
    _known(name, SPANS)
    return jax.profiler.TraceAnnotation(name)


def _known(name: str, names) -> None:
    # one list of names, so that tests and readers of a trace find them all
    if name not in names:
        raise ValueError(f"{name!r} is not one of {names}")
