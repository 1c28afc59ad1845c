"""Share (%) of its roofline that the grouped routed-expert kernel reaches.

The least time of the routed experts' part of a tick (the family's
``expert_need``: each live slot's top-k expert rows, and the packed words
of top-k experts a MoE layer, the least any routing reads), averaged over
the window's ticks, over the kernel's device time per decode execution
(``decode_expert_ms``).  None where the kernel is not in the trace or the
family counts no experts.
"""

from bench.spec import ROOT, load_module

DECODE_PROGRAM = "jit__decode"
KERNEL = "expert_decode_qmm"


def read(run):
    runs = run.trace.program_ns.get(DECODE_PROGRAM) if run.trace else None
    secs = dict(run.trace.top_ops).get(KERNEL) if run.trace else None
    ticks = [t for t in run.ticks if run.in_window(t.t) and t.live]
    if not runs or secs is None or not ticks:
        return None
    config = run.need.args[0]  # the need is the family's decode_need of the config
    family = load_module(ROOT / "bench" / "families" / f"{config['family']}.py")
    need = getattr(family, "expert_need", None)
    if need is None:
        return None
    peak = run.peak
    least = sum(
        max(ops / peak["int8_ops_per_s"], nbytes / peak["hbm_bytes_per_s"])
        for ops, nbytes in (need(config, t.live) for t in ticks)
    ) / len(ticks)
    return 100.0 * least / (secs / len(runs))
