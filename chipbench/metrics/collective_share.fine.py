"""Share of the device's busy time at the finest sweep point that its
collective operations take: their self time in the fine window over the
window's busy time, each averaged over the cell's chips.  Times the
step's busy time (``step_us.fine``), it is what the exchange between
ranks costs a timestep."""
LAYER = "communication"
UNIT = "%"
MOVES = "metg_us"


def read(windows):
    w = windows["fine"]
    if w.trace.busy_ns <= 0:
        return None
    return 100.0 * w.trace.collective_ns / w.trace.busy_ns
