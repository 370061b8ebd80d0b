"""The trace reduction on a recorded TPU v5e profile and on hand-made
events.

``data/gcn_probe.xplane.pb`` was recorded on one TPU v5e: three jitted
GCN training steps (128 → 64 → 40) on an 8,192-node graph, each under a
``StepTraceAnnotation("step")``, then a 10 ms host sleep under a
``TraceAnnotation("serve.tick")``.
"""
import os

import pytest

from chipbench import trace

PROBE = os.path.join(os.path.dirname(__file__), "data",
                     "gcn_probe.xplane.pb")


@pytest.fixture(scope="module")
def probe():
    return trace.events(PROBE)


def test_events_read_device_ops_and_host_spans(probe):
    host = [e for e in probe if e.where == "host"]
    dev = [e for e in probe if e.where == "device:0"]
    assert [e.name for e in host] == ["step"] * 3 + ["serve.tick"]
    assert len(dev) == 258
    names = {e.name for e in dev}
    assert {"paramspmm_v2_k424_r32_d128", "paramspmm_v2_k424_r32_d128_relu",
            "fusion"} <= names
    assert not any(n.startswith("%") or "=" in n for n in names)


def test_reduce_probe(probe):
    red = trace.reduce_events(probe)
    host = [e for e in probe if e.where == "host"]
    w0 = min(e.start_ns for e in host)
    w1 = max(e.start_ns + e.dur_ns for e in host)
    assert red["window_s"] == pytest.approx((w1 - w0) * 1e-9)
    spmm = sum(min(e.start_ns + e.dur_ns, w1) - max(e.start_ns, w0)
               for e in probe if e.name.startswith("paramspmm_"))
    assert trace.seconds_by_prefix(red["op_seconds"], "paramspmm_") == \
        pytest.approx(spmm * 1e-9)
    assert 0 < red["busy_s"] < red["window_s"]
    # the kernels are nearly all of the busy time, and the sleep under
    # serve.tick is the longest gap
    assert spmm * 1e-9 > 0.95 * red["busy_s"]
    assert red["gaps"][0][0] == "serve.tick"
    assert red["gaps"][0][1] > 0.010
    assert [g[1] for g in red["gaps"]] == sorted(
        (g[1] for g in red["gaps"]), reverse=True)
    idle = trace.idle_share(red)
    assert idle == pytest.approx(100 * (1 - red["busy_s"] / red["window_s"]))
    bd = trace.breakdown(red)
    assert bd["device_ops"][0][0] == "paramspmm_v2_k424_r32_d128"
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_reduce_hand_made_events():
    E = trace.Event
    evs = [E("host", "step", 0, 100), E("host", "serve.tick", 100, 100),
           E("device:0", "paramspmm_a", 10, 30),
           E("device:0", "fusion", 30, 30),        # overlaps: union 10..60
           E("device:0", "sddmm_b", 150, 20),
           E("device:0", "paramspmm_a", 190, 50)]  # clipped at 200
    red = trace.reduce_events(evs)
    assert red["window_s"] == pytest.approx(200e-9)
    assert red["busy_s"] == pytest.approx((50 + 20 + 10) * 1e-9)
    assert red["op_seconds"]["paramspmm_a"] == pytest.approx(40e-9)
    assert trace.seconds_by_prefix(red["op_seconds"], "sddmm") == \
        pytest.approx(20e-9)
    # gaps 60..150 (mostly under serve.tick), 170..190, 0..10
    assert red["gaps"] == [("serve.tick", pytest.approx(90e-9)),
                           ("serve.tick", pytest.approx(20e-9)),
                           ("step", pytest.approx(10e-9))]
    assert trace.idle_share(red) == pytest.approx(60.0)


@pytest.mark.parametrize("text,name", [
    ("%fusion.24 = f32[8,64]{1,0} fusion(f32[8,128] %c)", "fusion"),
    ("%paramspmm_v2_k56_r32_d128_relu.3 = f32[8,128] custom-call()",
     "paramspmm_v2_k56_r32_d128_relu"),
    ("%sddmm_softmax_v2_k424_w16_d128 = (f32[2]) custom-call()",
     "sddmm_softmax_v2_k424_w16_d128"),
    ("copy-start.10", "copy-start"),
])
def test_op_name(text, name):
    assert trace.op_name(text) == name
