"""Device time per outer round of the recorded objective value and NNZ:
the ops of the named scope `pscope.objective`, less its collectives.
Read by bench/phases.py from the run's trace."""
import phases


def read(ctx):
    return phases.ms_per_round(ctx, "pscope.objective")
