"""The trace reduction and the work model, on a synthetic trace and known
shapes."""

import sys
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import trace, work  # noqa: E402


def ev(name, start, end, **stats):
    return NS(name=name, start_ns=start, end_ns=end, stats=list(stats.items()))


def profile():
    ops = [
        ev("%fusion.1 = s32[8]{0} fusion(s32[8]{0} %a)", 100, 150),
        ev("%while.3 = (s32[]) while((s32[]) %t)", 150, 400),
        ev("%closed_call.7 = (f32[8,1,128]) custom-call(s32[8,4096] %ids)", 160, 390),
        ev("%sort.2 = (s32[8,8]) sort(s32[8,8] %c)", 500, 600),
        ev("%wl1_scan_topk_pallas.1 = (f32[8,128]) custom-call(f32[64,256] %d)", 700, 800),
        ev("%alsh_project_pallas.1 = f32[8,384] custom-call(s32[8,128] %e)", 820, 840),
        ev("%fusion.9 = f32[8]{0} fusion(f32[8]{0} %f)", 950, 1200),  # runs past the window
    ]
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_query", 100, 840)]),
        NS(name="XLA Ops", events=ops)])
    megascale = NS(name="/device:CUSTOM:Megascale Trace", lines=[])
    other_chip = NS(name="/device:TPU:1", lines=[NS(name="XLA Ops", events=[ev("x", 0, 1e6)])])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev(trace.WINDOW_SPAN, 50, 1000),
        ev("client.query", 60, 450),
        ev("client.prepare", 400, 500),
        ev("client.query", 460, 850),
        ev("unrelated", 0, 5),
    ])])
    return NS(planes=[host, megascale, other_chip, device])


def test_reduce_profile():
    r = trace.reduce_profile(profile())
    assert r.window_s == pytest.approx(950e-9)
    # union of [100, 400], [500, 600], [700, 800], [820, 840], [950, 1000]:
    # the last op is cut at the window's end
    assert r.busy_s == pytest.approx(570e-9)
    assert r.kernel_s("gather_rerank") == pytest.approx(230e-9)
    assert r.kernel_s("exact_scan") == pytest.approx(100e-9)
    assert r.kernel_s("project") == pytest.approx(20e-9)
    # fusion.1 50, the while's own 20 (without the kernel inside), sort.2
    # 100, fusion.9 50
    assert r.xla_ops_s() == pytest.approx(220e-9)
    b = r.breakdown()
    assert b["device_ops"][0] == ["gather_rerank", pytest.approx(230e-9)]
    assert dict(b["device_ops"])["while.3"] == pytest.approx(20e-9)
    idle = dict(b["idle_gaps"])
    # gaps by their midpoints: [50, 100], [600, 700] and [800, 820] in a
    # query span, [400, 500] in the prepare span (the innermost of two),
    # [840, 950] in no client span
    assert idle["client.query"] == pytest.approx((50 + 100 + 20) * 1e-9)
    assert idle["client.prepare"] == pytest.approx(100e-9)
    assert idle["outside client spans"] == pytest.approx(110e-9)


def test_union_and_gaps():
    assert trace.union_seconds([(0, 10), (5, 20), (30, 40)]) == pytest.approx(30e-9)
    assert trace.gaps([(0, 10), (5, 20), (30, 40)], 0, 50) == [(20, 30), (40, 50)]
    assert trace.union_seconds([]) == 0


@pytest.mark.parametrize("host_window", [None, (5000, 6000)])
def test_window_falls_back_to_device_span(host_window):
    """Without the host's window span, or with one on another clock that
    holds no device operation, the window is the device's first to last
    operation."""
    p = profile()
    events = p.planes[0].lines[0].events
    p.planes[0].lines[0].events = [e for e in events if e.name != trace.WINDOW_SPAN]
    if host_window:
        p.planes[0].lines[0].events.append(ev(trace.WINDOW_SPAN, *host_window))
    r = trace.reduce_profile(p)
    assert (r.start_ns, r.end_ns) == (100, 1200)
    assert r.busy_s == pytest.approx(770e-9)


def test_peaks_known_and_unknown():
    assert work.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")


def test_gather_rerank_bytes():
    # f32: 1000 candidates x 128 x 4 B, plus 2 queries' vectors and answers
    assert work.gather_rerank_bytes(1000, 2, 128, "f32", 0, 10) == (
        1000 * 512 + 2 * 128 * 8 + 2 * 10 * 8)
    # int8 screen: the survivors are read again, with the scales
    assert work.gather_rerank_bytes(1000, 2, 128, "int8", 20, 10) == (
        1000 * 128 + 2 * 128 * 8 + 2 * 10 * 8 + 2 * 20 * 128 + 2 * 128 * 8 + 128 * 4
        + 2 * 20 * 8)


def test_scan_work_and_roofline():
    assert work.scan_bytes(1000, 128, 16, 2, 10) == 2 * 1000 * 512 + 16 * (1024 + 80)
    assert work.scan_ops(1000, 128, 16) == 3 * 16 * 1000 * 128
    # 819 MB in 2 s at 819 GB/s is 0.05 %
    assert work.roofline_pct(819e6, 2.0, "TPU v5 lite") == pytest.approx(0.05)
    assert work.roofline_pct(1.0, 0.0, "TPU v5 lite") is None
