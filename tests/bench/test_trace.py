"""The trace reduction on a small trace recorded on one TPU v5e: three
executions of a jitted program holding a Pallas kernel ("double_kernel")
and one fusion, each inside a `bench.step` span, all inside
`bench.window` (recorded with bench.harness.profiled)."""
from pathlib import Path

import pytest

from bench import readers
from bench import trace as tr

SMALL = Path(__file__).parent / "data" / "small_v5e.xplane.pb"


@pytest.fixture(scope="module")
def red():
    return tr.load(SMALL)


def test_planes_and_names(red):
    assert list(red.devices) == ["/device:TPU:0"]
    d = red.device
    assert [m[0] for m in d.modules] == ["jit__lambda(3227780182303872390)"] * 3
    # HLO text after the instruction name is cut off
    assert [o[0] for o in d.ops] == ["double_kernel.1", "fusion"] * 3
    assert [h[0] for h in red.host if h[0].startswith("bench.")] == [
        "bench.window", "bench.step", "bench.step", "bench.step"]


def test_busy_and_idle_add_up_to_the_window(red):
    lo, hi = tr.host_window(red.host, "bench.window")
    assert (lo, hi) == (45000610.0, 47733870.0)
    ops = tr.within(red.device.ops, lo, hi)
    busy = tr.busy_ns(ops)
    assert busy == 982 + 1538 + 929 + 1543 + 732 + 1536
    gaps = tr.idle_gaps(red.device.ops, red.host, lo, hi)
    assert sum(s for _, s in gaps) * 1e9 == pytest.approx(hi - lo - busy)
    assert gaps[0][0] == "bench.step"


def test_kernel_time_and_roofline(red):
    lo, hi = tr.host_window(red.host, "bench.window")
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    ctx = readers.Ctx(None, red, lo, hi, peaks)
    execs = ctx.executions(kernel="double_kernel")
    assert len(execs) == 3
    assert ctx.executions(kernel="quant_matmul") == []
    # 256x256 f32 read and written: 524288 bytes a call
    share = ctx.roofline("double_kernel", [(65536.0, 524288.0)] * 3)
    assert share == pytest.approx(100 * 3 * 524288 / 819e9 /
                                  ((982 + 929 + 732) * 1e-9))
    assert ctx.roofline("double_kernel", [(1.0, 1.0)] * 2) is None
    assert ctx.busy_s() == pytest.approx(7260e-9)


def test_union_and_inside():
    ev = [("a", 0, 10), ("b", 5, 10), ("c", 20, 5), ("d", 24, 1)]
    assert tr.union(ev) == [(0, 15), (20, 25)]
    assert tr.busy_ns(ev) == 20
    spans = [("m", 0, 12), ("m", 19, 10)]
    assert tr.inside(ev, spans) == [[ev[0], ev[1]], [ev[2], ev[3]]]
    assert tr.top_ops(ev + [("a", 30, 10)], 2) == [["a", 20e-9],
                                                   ["b", 10e-9]]
    assert readers.is_kernel("quant_matmul.48", "quant_matmul")
    assert not readers.is_kernel("quant_matmul_fused.1", "quant_matmul")
    assert tr.op_name("%fusion.63 = f32[64]{0} fusion(bf16[64])") == \
        "fusion.63"
