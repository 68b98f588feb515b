"""Compile: programs JAX compiled or loaded from its persistent cache
between the window's edges (``backend_compile_duration`` events, which
JAX reports for both; see ``compileclock.CompileClock``).  A warm-up that
covers every shape the window runs leaves this at 0."""


def read(w):
    return w.compiles["backend_compiles"]
