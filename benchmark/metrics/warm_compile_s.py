"""Seconds of the first warm-up step (trace, lower, compile or cache read,
first dispatch) beyond one steady step: the first warm-up step's span minus
the median step of the window."""
import statistics


def read(ctx):
    run = ctx["run"]
    first = (ctx["samples"].get("warm_round")
             or ctx["samples"].get("warm_request"))
    steady = getattr(run, "step_seconds", None)
    if not first or not steady:
        return None
    return first[0] - statistics.median(steady)
