"""The trace reduction, against a small trace recorded on a v5e chip
(three calls of a jitted three-matmul program between
``bench.dispatch`` / ``bench.fence`` annotations; PR 24's probe) and
against synthetic events."""

import os

import pytest

from benchmark.reduce import xplane as X

TINY = os.path.join(os.path.dirname(__file__), "data",
                    "tiny_v5e.xplane.pb")


@pytest.fixture(scope="module")
def tiny():
    return X.load(TINY)


def test_recorded_trace_planes(tiny):
    assert [d.name for d in tiny.devices] == ["/device:TPU:0"]
    dev = tiny.devices[0]
    assert len(dev.modules) == 3
    assert {X.module_name(m[0]) for m in dev.modules} == {"jit_tiny"}
    assert len(dev.ops) == 12 and len(dev.async_ops) == 3


def test_recorded_trace_busy_and_window(tiny):
    # three calls of ~6.4 us each, 3 ms apart (the probe slept)
    assert X.busy_s(tiny) == pytest.approx(1.9317e-05, rel=1e-3)
    assert tiny.window_s == pytest.approx(6.531e-03, rel=1e-3)
    assert 0.99 < 1 - X.busy_s(tiny) / tiny.window_s < 1.0


def test_recorded_trace_host_spans(tiny):
    names = [s[0] for s in tiny.host_spans]
    assert names == ["bench.dispatch", "bench.fence"] * 3


def test_recorded_trace_breakdown(tiny):
    top = X.top_ops(tiny, 10)
    assert top[0][0] == "convolution_tanh_fusion"
    assert top[0][1] == pytest.approx(1.927e-05, rel=1e-3)
    gaps = X.idle_gaps(tiny, 10)
    assert gaps[0][0].endswith("host:bench.fence")
    assert sum(g[1] for g in gaps) == pytest.approx(
        tiny.window_s - X.busy_s(tiny), rel=1e-6)


@pytest.mark.parametrize("text,kind", [
    ("%add_add_fusion.2 = bf16[24,1024]{1,0} fusion(...)",
     "add_add_fusion"),
    ("%fusion = f32[8]{0} fusion(%p)", "fusion"),
    ("%all-gather-start.3 = (bf16[4]) all-gather-start(%x)",
     "all-gather-start"),
    ("%while.7 = (s32[]) while(%t)", "while"),
])
def test_op_kind(text, kind):
    assert X.op_kind(text) == kind


@pytest.mark.parametrize("text,yes", [
    ("%all-gather-start.3 = (bf16[4]) all-gather-start(%x)", True),
    ("%reduce-scatter.1 = f32[4] reduce-scatter(%x)", True),
    ("%all-reduce-done = f32[4] all-reduce-done(%x)", True),
    ("%fusion.3 = f32[4] fusion(%x), kind=kCustom, "
     "calls=all-reduce-scatter.1", True),
    ("%fusion.9 = f32[4] fusion(%x), kind=kLoop, calls=fused_add", False),
    ("%copy-start.1 = (f32[4]) copy-start(%x)", False),
])
def test_is_collective(text, yes):
    assert X.is_collective(text) is yes


def test_mosaic_signature():
    text = ('%tpu_custom_call.21 = (bf16[288,1024,64]{2,1,0:T(8,128)}, '
            'f32[288,1,1024]{2,1,0}) custom-call(bf16[288,1024,64]{2,1,0} '
            '%b), custom_call_target="tpu_custom_call"')
    assert X.is_mosaic(text)
    assert X._signature(text) == "bf16_288_1024_64+f32_288_1_1024"
    assert not X.is_mosaic(
        '%custom-call.4 = f32[1] custom-call(), '
        'custom_call_target="AllocateBuffer"')
    assert X.module_name("jit_step(88701516715663737)") == "jit_step"


def test_union_subtract():
    u = X.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)])
    assert u == [(0, 3), (5, 8)]
    assert X.total(u) == 6
    assert X.subtract([(0, 10)], u) == [(3, 5), (8, 10)]
    assert X.subtract([(0, 2), (4, 6)], [(1, 5)]) == [(0, 1), (5, 6)]
    assert X.subtract([(0, 2)], []) == [(0, 2)]


def test_self_times_take_a_while_bodys_ops_out():
    ops = [("%while.1 = () while()", 0.0, 100.0),
           ("%fusion.1 = f32[] fusion()", 10.0, 30.0),
           ("%fusion.2 = f32[] fusion()", 50.0, 40.0),
           ("%copy.1 = f32[] copy()", 120.0, 5.0)]
    got = dict(X.self_times(ops))
    assert got["%while.1 = () while()"] == 30.0
    assert got["%fusion.2 = f32[] fusion()"] == 40.0
    dev = X.DeviceTrace("/device:TPU:0", ops,
                        [("jit_step(1)", 0.0, 125.0)])
    trace = X.windowed([dev], [])
    assert X.busy_s(trace) == pytest.approx(105e-9)
    assert dict(map(tuple, X.top_ops(trace)))["fusion"] == \
        pytest.approx(70e-9)


def test_window_marks_clip_the_device_events():
    dev = X.DeviceTrace(
        "/device:TPU:0",
        [("%fusion.1 = f32[] fusion()", 0.0, 100.0),
         ("%fusion.2 = f32[] fusion()", 150.0, 100.0)],
        [("jit_pool_step(1)", 0.0, 100.0),
         ("jit_pool_step(1)", 150.0, 100.0)])
    spans = [(X.WINDOW_START, 50.0, 1.0), ("bench.send", 120.0, 5.0),
             (X.WINDOW_END, 200.0, 1.0)]
    trace = X.windowed([dev], spans)
    assert (trace.t0_ns, trace.t1_ns) == (50.0, 200.0)
    assert X.busy_s(trace) == pytest.approx(100e-9)
    assert [m[2] for m in X.module_events(trace, "jit_pool_step")[0]] \
        == [50.0, 50.0]
    gaps = X.idle_gaps(trace)
    assert gaps == [["jit_pool_step - jit_pool_step host:bench.send",
                     pytest.approx(50e-9)]]


def test_a_trace_with_no_device_event_is_refused():
    with pytest.raises(ValueError, match="no operation ran"):
        X.windowed([X.DeviceTrace("/device:TPU:0", [], [])], [])
