"""Device busy time per outer round outside the epoch kernel and outside
collectives: the epoch plan and gathers, the anchor gradient, the
objective and the averaging."""


def read(ctx):
    ns = ctx["chip"]["other_ns"]
    return ns / ctx["rounds"] / 1e6 if ns else None
