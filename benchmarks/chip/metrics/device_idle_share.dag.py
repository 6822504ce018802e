"""Share of the traced window in which no operation ran on the chip, in
%.  Layer: device.  Moves ``taos_per_s``."""


def read(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 100.0 * run.trace.idle_share
