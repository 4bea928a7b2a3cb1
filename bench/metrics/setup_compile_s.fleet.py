"""Seconds of set-up spent tracing, lowering and compiling, compile-cache
loads included (the backend compile phase wraps the load), counted by the
program's ``jax.monitoring`` listener before the window's first slot call.
Phases nest (a jitted function traced inside another), so their union is
counted."""
from program_obs import program_obs, union_s, window_calls


def read(ctx):
    calls = window_calls(ctx)
    if calls is None:
        return None
    obs, start = program_obs(), calls[0][0]
    phases = {obs.TRACE, obs.LOWER, obs.COMPILE}
    spans = [(t - 1e9 * s, t) for t, ev, s in obs.compile_events()
             if ev in phases and t <= start]
    return union_s(spans) if spans else None
