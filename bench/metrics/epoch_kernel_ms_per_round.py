"""Device time of the fused lazy-epoch kernel per outer round."""


def read(ctx):
    ns = ctx["chip"]["kernel_ns"]
    return ns / ctx["rounds"] / 1e6 if ns else None
