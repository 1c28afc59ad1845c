"""Device time per execution of the engine's jitted decode program.

From the profiler trace: the ``XLA Modules`` events of the program named
``jit__decode`` (``ServeEngine``'s ``jax.jit(_decode)``).
"""

DECODE_PROGRAM = "jit__decode"


def read(run):
    runs = run.trace.program_ns.get(DECODE_PROGRAM) if run.trace else None
    return sum(runs) / len(runs) / 1e6 if runs else None
