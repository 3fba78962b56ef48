"""Share of the traced window at the finest sweep point in which no
operation ran on the device (averaged over the cell's chips)."""
LAYER = "device"
UNIT = "%"
MOVES = "metg_us"


def read(windows):
    w = windows["fine"]
    if w.trace.window_s <= 0 or w.trace.busy_ns <= 0:
        return None
    return 100.0 * (1.0 - w.trace.busy_s / w.trace.window_s)
