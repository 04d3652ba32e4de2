"""Host time per solve before its trajectory is dispatched: the program
spans `solve.prepare`, `mesh.shards` and `mesh.prepare` (bench/phases.py)
over the number of `solve.<solver>` spans."""
import phases


def read(ctx):
    return phases.prepare_ms_per_solve()
