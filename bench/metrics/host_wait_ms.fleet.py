"""Host time per slot after ``FleetEngine.run`` returns, until the slot's
result is ready: launch, execution, completion and output buffers. Each
call's host-clock latency less its ``fleet.run`` span, so that with
``dispatch_ms.fleet`` it makes up the window's host time per slot."""
from program_obs import window_calls


def read(ctx):
    calls = window_calls(ctx)
    if calls is None:
        return None
    return 1e3 * sum(t - 1e-9 * d for _, d, t in calls) / len(calls)
