"""Median of the gaps between consecutive output tokens of a request.

All requests pooled; a gap counts when both of its tokens fall in the
window.  Most gaps are one decode tick and its host work (sampling, the
logits' transfer); a gap that spans an admission, which stalls every active
slot, is one sample among hundreds.
"""

import numpy as np


def read(run):
    gaps = [
        b - a
        for r in run.requests
        for a, b in zip(r.token_times, r.token_times[1:])
        if run.open <= a and b < run.close
    ]
    return float(np.median(gaps) * 1e3) if gaps else None
