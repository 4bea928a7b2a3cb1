"""Seconds of set-up spent building the slice parameters and the initial
state (spans ``fleet.from_jobs`` and ``fleet.init``) before the window's
first slot call."""
from program_obs import program_obs, union_s, window_calls


def read(ctx):
    calls = window_calls(ctx)
    if calls is None:
        return None
    obs, start = program_obs(), calls[0][0]
    spans = [(s, s + d) for name in ("fleet.from_jobs", "fleet.init")
             for s, d in obs.spans(name) if s + d <= start]
    return union_s(spans) if spans else None
