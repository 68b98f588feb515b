"""Device: share of the traced window in which no operation ran on the
chip, 100 * (1 - busy / window), in a closed-loop cell."""


def read(w):
    if w.trace is None:
        return None
    return 100.0 * (1.0 - w.trace["busy_s"] / w.trace["window_s"])
