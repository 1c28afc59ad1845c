"""The trace reduction on a small recorded trace: busy union, idle share,
device time per program, idle gaps on the engine's clock."""

import pytest

from bench import trace as T
from bench.spec import ROOT, load_module

# Two decode executions and an eager prefill scan on one device, with the
# operations inside them (starts and durations in ns), shaped like the
# ``XLA Modules`` / ``XLA Ops`` lines of a TPU v5e trace.
DEVICE = T.Device(
    modules=[
        (1_000, 100, "jit__decode(15569214447562970945)"),
        (1_150, 50, "jit_scan(6716087165648592643)"),
        (1_300, 100, "jit__decode(15569214447562970945)"),
        (1_700, 10, "jit__decode(15569214447562970945)"),  # after the window
    ],
    ops=[
        (1_000, 60, "%fusion.31 = s32[8,4096,8]{2,1,0} fusion(s8[8,4096,8,128] %p)"),
        (1_050, 50, "%convert_convert_fusion.14 = s8[14336,4096] fusion(u32[448,14336] %w)"),
        (1_150, 50, "%while.6 = (s32[], bf16[1,1024,4096]) while(%t)"),
        (1_300, 100, "%fusion.31 = s32[8,4096,8]{2,1,0} fusion(s8[8,4096,8,128] %p)"),
        (1_700, 10, "%fusion.31 = s32[8,4096,8]{2,1,0} fusion(s8[8,4096,8,128] %p)"),
    ],
)


def test_union_merges_overlaps():
    assert T.union([(5, 9), (0, 3), (2, 4), (9, 10)]) == [(0, 4), (5, 10)]


def test_names():
    assert T.program_name("jit__decode(15569214447562970945)") == "jit__decode"
    assert T.op_name("%convert_convert_fusion.14 = s8[1] fusion(%x)") == "convert_convert_fusion"
    assert T.op_name("%while.6 = (s32[]) while(%t)") == "while"


def test_summary_of_recorded_window():
    # window 1000..1500 ns; the anchor sits at 1000 ns and 2.0 s on the engine's clock
    s = T.summarize([DEVICE], 1_000, 1_500, anchor_ns=1_000, anchor_t=2.0)
    assert s.window_s == pytest.approx(500e-9)
    assert s.busy_s == pytest.approx(250e-9)  # [1000,1100] + [1150,1200] + [1300,1400]
    assert s.program_ns == {"jit__decode": [100, 100], "jit_scan": [50]}
    assert dict(s.top_ops) == pytest.approx({"fusion": 160e-9, "convert_convert_fusion": 50e-9, "while": 50e-9})
    # gaps, longest first: 1200..1300, 1400..1500, 1100..1150
    assert [round(g, 12) for _, g in s.gaps] == [100e-9, 100e-9, 50e-9]
    assert s.gaps[-1][0] == pytest.approx(2.0 + 100e-9)


def test_idle_share_and_decode_time_readers():
    s = T.summarize([DEVICE], 1_000, 1_500)

    class Run:
        trace = s

    idle = load_module(ROOT / "bench" / "metrics" / "idle_share.py").read(Run)
    step = load_module(ROOT / "bench" / "metrics" / "decode_step_ms.py").read(Run)
    assert idle == pytest.approx(50.0)
    assert step == pytest.approx(100e-6)


def test_no_device_plane_reads_nothing():
    s = T.summarize([], 0, 1_000)

    class Run:
        trace = s

    assert load_module(ROOT / "bench" / "metrics" / "decode_step_ms.py").read(Run) is None

    assert load_module(ROOT / "bench" / "metrics" / "idle_share.py").read(Run) is None
