"""Share of the slot program's EC-pair problems that are ragged padding:
100 x (1 - ``fleet.pairs_real`` / ``fleet.pairs_compiled``), the gauges
``FleetEngine.from_jobs`` sets for the fleet it builds. Read only with the
window's slot calls in the program's spans (so the gauges are of the fleet
that made them); a program without gauges gives None."""
from program_obs import program_obs, window_calls


def read(ctx):
    obs = program_obs()
    if window_calls(ctx) is None or not hasattr(obs, "gauges"):
        return None
    g = obs.gauges()
    real, compiled = g.get("fleet.pairs_real"), g.get("fleet.pairs_compiled")
    if not compiled or real is None:
        return None
    return 100.0 * (1.0 - real / compiled)
