"""Device time per outer round of the cross-chip collectives on the
reader's chip: the CALL round's all-reduces (the anchor gradient's and
the iterates' `pmean`, the recorded objective's scalar sum), the union
of their events.  A one-chip run has none and reports nothing."""


def read(ctx):
    ns = ctx["chip"]["collective_ns"]
    return ns / ctx["rounds"] / 1e6 if ns else None
