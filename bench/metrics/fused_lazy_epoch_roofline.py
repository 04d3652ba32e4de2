"""The fused lazy-epoch kernel's share of its roofline: the least time
the chip needs to move the inner epoch's algorithmic bytes (bench/work.py)
at its HBM peak, over the kernel's device time.  Bound by memory."""
import work


def read(ctx):
    ns = ctx["chip"]["kernel_ns"]
    if not ns:
        return None
    least = work.least_seconds(
        ctx["epoch_bytes_per_chip_round"] * ctx["rounds"], ctx["peak"])
    return 100.0 * least / (ns / 1e9)
