"""Device time per outer round of the epoch gathers: the ops of the named
scope `pscope.gather`.
Read by bench/phases.py from the run's trace."""
import phases


def read(ctx):
    return phases.ms_per_round(ctx, "pscope.gather")
