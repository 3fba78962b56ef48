"""Task-body rate at the coarsest sweep point: the operations of the
traced runs over the device's busy time.  An achieved rate and no share:
no peak of the vector unit is published for the chip."""
LAYER = "task body"
UNIT = "GFLOP/s"
MOVES = "coarse_gflops"


def read(windows):
    w = windows["coarse"]
    if w.runs == 0 or w.trace.busy_ns <= 0:
        return None
    return w.point.flops * w.runs / w.trace.busy_s / 1e9
