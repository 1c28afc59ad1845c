"""Device time per execution of the engine's decode program spent in the
grouped routed-expert kernel (``kernels.binary_qmm.expert_decode_qmm``).

From the profiler trace: the kernel's op kind among the window's ten
costliest (``trace.Summary.top_ops``), over the ``jit__decode``
executions.  None where the kernel is not among them, as in a program
without it.

The profiler keeps a bounded number of trace buffers and drops the rest
(the device plane's ``dropped_traces`` stat): in a window of many short
ticks, each of thousands of ops, the trace ends before the window does.
Its ``XLA Modules`` and ``XLA Ops`` lines end together, and every recorded
decode execution has its ops, so both sums cover the same executions and
this is their mean.
"""

DECODE_PROGRAM = "jit__decode"
KERNEL = "expert_decode_qmm"


def read(run):
    runs = run.trace.program_ns.get(DECODE_PROGRAM) if run.trace else None
    secs = dict(run.trace.top_ops).get(KERNEL) if run.trace else None
    if not runs or secs is None:
        return None
    return secs / len(runs) * 1e3
