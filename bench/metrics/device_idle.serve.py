"""device_idle.serve: share of the traced window in which no op ran on
the chip, in a serving cell."""


def read(view):
    if view.trace.window_s() <= 0:
        return None
    return 100.0 * view.trace.idle_share()
