"""Host time per slot inside ``FleetEngine.run`` (span ``fleet.run``):
placing the arguments and dispatching the slot program, which returns
before the device is done."""
from program_obs import window_calls


def read(ctx):
    calls = window_calls(ctx)
    if calls is None:
        return None
    return 1e-6 * sum(d for _, d, _ in calls) / len(calls)
