"""The program's own trace: host spans and device scopes, one home.

A span is a ``jax.profiler.TraceAnnotation`` named ``wl1.<name>``; a scope
is a ``jax.named_scope`` named ``wl1.<name>``, which puts the stage into
the op metadata of every operation traced under it. The profiler is the
collector and its xplane file the export: with no trace running, a span is
an enter and an exit that do nothing. Capture one with
``jax.profiler.trace(dir)`` around the calls, or serve traces on demand
with ``jax.profiler.start_server(port)``.

Importing this module registers one ``gc.callbacks`` hook, which puts a
``wl1.gc`` span (args ``generation``, ``collected``) around every pass of
Python's collector, so a collector pause shows on the trace's clock.
"""

from __future__ import annotations

import gc

import jax

PREFIX = "wl1."

# host spans (Index.query and the collector)
QUERY = "query"  # args: seq, mode, b, k, storage, screen_keep
VALIDATE = "query.validate"
PLAN = "query.plan"
DISPATCH = "query.dispatch"  # args: compiled
GC = "gc"  # args: generation, collected

# device scopes (engine/pipeline.py)
EXACT_SCAN = "exact_scan"
PROJECT = "project"
WINDOW = "window"
DEDUPE = "dedupe"
SCREEN = "screen"
RERANK = "rerank"
STREAM = "stream"


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A host span ``wl1.<name>`` carrying ``args``; ``set_metadata`` adds
    args known only once the span is open."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **args)


def scope(name: str):
    """A device scope ``wl1.<name>`` over the operations traced inside."""
    return jax.named_scope(PREFIX + name)


_gc_span = None


def _on_gc(phase: str, info: dict) -> None:
    global _gc_span
    if phase == "start":
        _gc_span = span(GC, generation=info["generation"])
        _gc_span.__enter__()
    elif _gc_span is not None:
        _gc_span.set_metadata(collected=info["collected"])
        _gc_span.__exit__(None, None, None)
        _gc_span = None


gc.callbacks.append(_on_gc)
