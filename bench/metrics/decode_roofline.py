"""Share (%) of its roofline that the decode program reaches.

The least time a tick's needed work takes (``bench/families/<family>.py``:
the larger of operations over the int8 peak and bytes over HBM bandwidth),
averaged over the window's ticks, over the program's mean device time per
execution from the trace.  The need is counted from shapes and live
lengths, not from what the program reads, so this stays under 100%.
"""

DECODE_PROGRAM = "jit__decode"


def _least_time_s(ops, nbytes, peak):
    return max(ops / peak["int8_ops_per_s"], nbytes / peak["hbm_bytes_per_s"])


def read(run):
    runs = run.trace.program_ns.get(DECODE_PROGRAM) if run.trace else None
    ticks = [t for t in run.ticks if run.in_window(t.t) and t.live]
    if not runs or not ticks:
        return None
    least = sum(_least_time_s(*run.need(t.live), run.peak) for t in ticks) / len(ticks)
    measured = sum(runs) / len(runs) / 1e9
    return 100.0 * least / measured
