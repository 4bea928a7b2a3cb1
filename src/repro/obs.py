"""The program's own spans, gauges and compile counter, always on.

``span(name)`` times a block of host code twice: as a
``jax.profiler.TraceAnnotation``, so it lands on the host plane of a profiler
trace on the same clock as the device ops, and as ``(start_ns, dur_ns)``
from ``time.perf_counter_ns`` in a bounded ring per name, so it can be read
back with no profiler session. ``spans(name)`` returns a copy of the ring.

A ``jax.monitoring`` listener, registered once when this module is imported,
keeps ``(t_ns, event, seconds)`` of every tracing, lowering and backend
compile, and of every persistent-cache load (``compile_events()``). ``t_ns``
is the ``perf_counter_ns`` at which the phase ended. ``backend_compile``
wraps the cache load, so the two overlap.

Spans in the program:

  fleet.from_jobs, fleet.init   building a fleet's parameters and state
  fleet.run                     one ``FleetEngine.run`` call: placing the
                                arguments and dispatching the slot program
                                (it returns before the device is done)
  fleet.pack                    packing a tree state into the slot
                                program's one state buffer, inside
                                ``FleetEngine.run`` or ``.lower``: once per
                                chain of calls that starts from a tree

``gauge(name, value)`` keeps the last value set under ``name``; ``gauges()``
returns a copy of them all. Gauges in the program, set once per fleet by
``FleetEngine.from_jobs`` (never on the slot path):

  fleet.links_real       sum over slices of N_k * M_k (true CU-EC links)
  fleet.links_compiled   K * N_pad * M_pad (links the slot program computes)
  fleet.pairs_real       sum over slices of M_k (M_k - 1) / 2 (EC-pair
                         problems of the true shapes)
  fleet.pairs_compiled   K * M_pad (M_pad - 1) / 2 (pair problems the slot
                         program solves)
"""
from __future__ import annotations

import collections
import time

import jax

RING = 65536

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
COMPILE_EVENTS = (TRACE, LOWER, COMPILE, CACHE_LOAD)

_spans: dict[str, collections.deque] = collections.defaultdict(
    lambda: collections.deque(maxlen=RING))
_compiles: collections.deque = collections.deque(maxlen=RING)
_gauges: dict[str, float] = {}


class span:
    """``with span(name): ...`` records the block under ``name``."""

    __slots__ = ("name", "_note", "_t0")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self._note = jax.profiler.TraceAnnotation(self.name)
        self._note.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        self._note.__exit__(*exc)
        _spans[self.name].append((self._t0, t1 - self._t0))
        return False


def spans(name: str) -> list[tuple[int, int]]:
    """``(start_ns, dur_ns)`` of the newest ``RING`` spans of ``name``,
    oldest first."""
    return list(_spans.get(name, ()))


def gauge(name: str, value: float) -> None:
    """Set the gauge ``name`` to ``value`` (the last value is kept)."""
    _gauges[name] = value


def gauges() -> dict[str, float]:
    """A copy of every gauge, by name."""
    return dict(_gauges)


def compile_events() -> list[tuple[int, str, float]]:
    """``(end_ns, event, seconds)`` of the newest ``RING`` compile phases."""
    return list(_compiles)


def reset() -> None:
    _spans.clear()
    _compiles.clear()
    _gauges.clear()


def _on_duration(event: str, seconds: float, **_) -> None:
    if event in COMPILE_EVENTS:
        _compiles.append((time.perf_counter_ns(), event, seconds))


jax.monitoring.register_event_duration_secs_listener(_on_duration)
