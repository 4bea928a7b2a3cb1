"""What the readers of the program's own spans and counters (``repro.obs``)
share. The window's slot calls are the last ``counts["slots"]`` spans of
``fleet.run``, in order, each inside its call's host-clock latency
(``counts["latency_s"]``); the window starts at the first of them. A
program without ``repro.obs``, or spans that cannot be the window's, give
None."""
from __future__ import annotations


def program_obs():
    """The program's ``repro.obs`` module, or None where it has none."""
    try:
        from repro import obs
    except ImportError:
        return None
    return obs


def window_calls(ctx):
    """``[(start_ns, dur_ns, latency_s)]`` of the window's slot calls, or
    None."""
    obs = program_obs()
    n = ctx["counts"]["slots"]
    lat = ctx["counts"].get("latency_s") or []
    if obs is None or n == 0 or len(lat) != n:
        return None
    runs = obs.spans("fleet.run")
    if len(runs) < n:
        return None
    calls = runs[-n:]
    if any(d > 1e9 * t for (_, d), t in zip(calls, lat)):
        return None  # a span longer than its call is not the window's
    return [(s, d, t) for (s, d), t in zip(calls, lat)]


def union_s(intervals) -> float:
    """Seconds covered by ``[(start_ns, end_ns)]``, overlaps counted once."""
    total, end = 0.0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return 1e-9 * total
