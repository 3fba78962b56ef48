"""Device busy time per timestep at the finest sweep point: the cost of
one step of the backend's timestep loop when the task body is all but
empty."""
LAYER = "backends: timestep loop"
UNIT = "us"
MOVES = "metg_us"


def read(windows):
    w = windows["fine"]
    steps = w.runs * w.height
    if steps == 0 or w.trace.busy_ns <= 0:
        return None
    return w.trace.busy_s / steps * 1e6
