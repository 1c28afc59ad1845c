"""Share (%) of the int8 peak that the whole decode loop reaches.

Operations the window's decode ticks need (``bench/families/<family>.py``),
over the host-clock time from each tick to the one before it, over the
chip's int8 peak.  It counts every wait between ticks (admissions, sampling,
transfers), so it bounds any gain a kernel claims.
"""


def read(run):
    ops = secs = 0.0
    for prev, cur in zip(run.ticks, run.ticks[1:]):
        if run.in_window(cur.t) and cur.live:
            ops += run.need(cur.live)[0]
            secs += cur.t - prev.t
    return 100.0 * ops / secs / run.peak["int8_ops_per_s"] if secs else None
