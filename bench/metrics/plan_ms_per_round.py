"""Device time per outer round of the epoch plan: the ops of the named
scope `pscope.plan` (microbatch sampling, the plan build, and the shard
statics where an epoch rebuilds them).
Read by bench/phases.py from the run's trace."""
import phases


def read(ctx):
    return phases.ms_per_round(ctx, "pscope.plan")
