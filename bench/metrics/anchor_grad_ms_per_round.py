"""Device time per outer round of the anchor gradient (phase 1): the ops
of the named scope `pscope.anchor_grad`, less its all-reduce.
Read by bench/phases.py from the run's trace."""
import phases


def read(ctx):
    return phases.ms_per_round(ctx, "pscope.anchor_grad")
