"""Share (%) of the window that JAX reports spending in tracing, lowering and
backend compilation (a persistent-cache load included), by its monitoring
events (``jax.monitoring``)."""


def read(run):
    secs = sum(s for t, s in run.compile_events if run.in_window(t))
    return 100.0 * secs / run.seconds if secs else None
