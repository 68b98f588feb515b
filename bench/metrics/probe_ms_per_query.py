"""Probe kernels: device milliseconds of the probe kernels in the traced
window (the ``probe`` entry of ``kernels.json``) per query the window's
rate answers in its length (``Window.queries_answered``)."""


def read(w):
    n = w.queries_answered()
    if w.trace is None or not n or "probe" not in w.trace["kernel_s"]:
        return None
    return w.trace["kernel_s"]["probe"] * 1e3 / n
