"""Rounds a solve needs to reach the cell's relative objective gap,
interpolated in log(gap) from the solve's recorded history."""


def read(ctx):
    return ctx["rounds_to_gap"]
