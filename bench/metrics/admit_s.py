"""Mean time from a request's admission to its first token, over admissions
in the window.  Host clock; an admission ends in the logits' transfer, so it
holds the eager prefill's retrace, cache load, device work and the insert."""


def read(run):
    spans = [r.first - r.admitted for r in run.requests if run.in_window(r.admitted) and r.first is not None]
    return sum(spans) / len(spans) if spans else None
