"""Host time per solve spent placing each worker's shard on its chip:
the program span `mesh.shards` (bench/phases.py) over the number of
`solve.<solver>` spans.  A solve that places no shards reports
nothing."""
import phases


def read(ctx):
    summary = phases.traced_window()
    ns = summary["spans"].get("mesh.shards", {}).get("ns", 0.0)
    return ns / summary["solves"] / 1e6 if ns and summary["solves"] else None
