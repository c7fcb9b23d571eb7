"""The program's tap: host spans, host syncs, named device scopes, and
the record of launched device programs.

Everything here is on the profiler's clock. Capture a call with
``jax.profiler.trace(dir)`` and the spans appear on the calling
thread's line of the host plane, nested, beside the device operations
they launch:

- ``repro.count_butterflies``, ``repro.peel_tips``,
  ``repro.peel_tips_stored``, ``repro.peel_wings``: the public entry
  points (the root of a call);
- ``repro.rank`` (vertex ranking), ``repro.preprocess`` (the ranked
  CSR, or a peeling's global-id CSR), ``repro.plan`` (tile plans and
  peeling capacities): host preprocessing;
- ``repro.launch.<program>``: the dispatch of one device program
  through :func:`launch`; the program's device operations follow it;
- ``repro.fetch``: one device-to-host transfer (:func:`fetch`), so the
  number of these spans is the number of host syncs.

Inside the jitted loops, :func:`scope` names the phases in the compiled
HLO's ``op_name`` metadata (:data:`DEVICE_SCOPES`), which names every
fused instruction after a recompile renumbers it. A span costs under a
microsecond when no profiler runs (0.7-0.8 µs on a CPU host), a few
dozen of them a call; a scope costs nothing at run time: the compiled
code is the same.

``pipeline`` re-exports all of this; ``graph`` and ``ranking`` import it
from here, because ``pipeline`` imports them.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Optional

import jax

__all__ = [
    "DEVICE_SCOPES",
    "scope",
    "span",
    "traced",
    "fetch",
    "record_programs",
    "launch",
]

# The device phases named inside the jitted programs, innermost wins:
# counting (run_fused_pallas_program) — ``offsets`` (per-slot wedge
# counts, their prefix, the zeroed accumulators), ``recover`` (each
# lane's wedge from the offsets), ``match`` (endpoint-pair groups: the
# wedge_fused kernel or a vertex tile's dense table), ``accumulate``
# (the per-lane scatter-adds, and the widening of their int32 partials
# into 64-bit counts); peeling (device_round_loop,
# stream_tiles) — ``select`` (extract-min, bucket choice, the peel
# set), ``recover`` (the frontier's level-1 and level-2 searches),
# ``member`` (a wing triple's edge-membership search), ``subtract``
# (aggregating a tile's decrements), ``bucket_update`` (applying them).
DEVICE_SCOPES = (
    "offsets", "recover", "match", "accumulate",
    "select", "subtract", "bucket_update", "member",
)

SPAN_PREFIX = "repro."


def scope(name: str):
    """``jax.named_scope`` for one of :data:`DEVICE_SCOPES`."""
    if name not in DEVICE_SCOPES:
        raise ValueError(f"unknown device scope {name!r}; "
                         f"known: {DEVICE_SCOPES}")
    return jax.named_scope(name)


def span(name: str):
    """A host span ``repro.<name>`` on the profiler's clock."""
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


def traced(name: str):
    """Decorator: run the function inside ``span(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


def fetch(x):
    """``jax.device_get`` inside a ``repro.fetch`` span: every host sync
    of the count and peel paths goes through here."""
    with span("fetch"):
        return jax.device_get(x)  # looked up per call: tests count it


_RECORDED: Optional[list] = None


@contextlib.contextmanager
def record_programs():
    """Record every device program launched through :func:`launch`
    while the block runs, as ``(program, args, kwargs)`` triples — so a
    caller can lower and compile exactly what an entry point ran
    (``program.lower(*args, **kwargs).compile().as_text()``) and see
    which kernels and scopes it holds. Recording keeps the arguments
    alive until the list is dropped."""
    global _RECORDED
    prev, _RECORDED = _RECORDED, []
    try:
        yield _RECORDED
    finally:
        _RECORDED = prev


def launch(program, *args, **kwargs):
    """Run a jitted device program inside a ``repro.launch.<name>``
    span, recording it when :func:`record_programs` is active."""
    if _RECORDED is not None:
        _RECORDED.append((program, args, kwargs))
    with span("launch." + getattr(program, "__name__", "program")):
        return program(*args, **kwargs)
