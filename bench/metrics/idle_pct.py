"""Share of the traced window in which no operation ran on the chip."""


def read(ctx):
    chip = ctx["chip"]
    return 100.0 * (1.0 - chip["busy_ns"] / chip["window_ns"])
