"""Device time per outer round of the cooperative averaging (phase 3):
the ops of the named scope `pscope.average`, less its all-reduce.
Read by bench/phases.py from the run's trace."""
import phases


def read(ctx):
    return phases.ms_per_round(ctx, "pscope.average")
