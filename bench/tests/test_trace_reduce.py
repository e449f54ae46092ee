"""``trace_reduce``: busy, idle, top ops and idle gaps, on made-up events
and on a small trace recorded on a TPU v5 lite chip
(``tools/record_trace.py``, kept as ``data/small.xplane.pb``)."""
from __future__ import annotations

import os

import pytest

import trace_reduce

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "data", "small.xplane.pb")


def test_reduce_of_known_events():
    devices = {"/device:TPU:0": [("fusion.1", 100, 300), ("copy.2", 250, 400),
                                 ("fusion.1", 600, 700),
                                 ("fusion.1", 950, 1200)]}
    spans = [("bench.window", 0, 1000), ("bench.forward", 0, 500),
             ("bench.next_input", 500, 650), ("bench.forward", 650, 1000)]
    r = trace_reduce.reduce(devices, spans)
    assert r["window_s"] == pytest.approx(1000e-9)
    # busy: [100,400) + [600,700) + [950,1000) clipped to the window
    assert r["busy_s"] == pytest.approx(450e-9)
    assert r["idle_share"] == pytest.approx(0.55)
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(350e-9)]
    assert r["idle_gaps"][0] == ["bench.forward", pytest.approx(250e-9)]
    labels = sorted(g[0] for g in r["idle_gaps"])
    assert labels == ["bench.forward", "bench.forward", "bench.next_input"]
    assert r["op_classes"] == [["fusion", pytest.approx(350e-9)],
                               ["copy", pytest.approx(150e-9)]]


def test_hlo_names_are_shortened_and_classed():
    full = ("%slice.566 = f32[64,64,56,56]{3,1,2,0:T(8,128)} slice(f32[64,"
            "64,113,113]{3,1,2,0:T(8,128)} %pad.44), slice={[0:64:1], "
            "[0:64:1], [2:113:2], [2:113:2]}")
    assert trace_reduce.short_name(full) == \
        "%slice.566 = f32[64,64,56,56] slice(f32[64,64,113,113] %pad.44)"
    assert trace_reduce.op_class(full) == "slice"
    fusion = ("%convolution_add_fusion.5 = f32[64,64,224,224]{3,0,2,1:T(8,"
              "128)} fusion(f32[64,64,3,3]{1,0,3,2:T(8,128)S(1)} %copy-done"
              ".14), kind=kOutput, calls=%fused_computation.6")
    assert trace_reduce.short_name(fusion) == (
        "%convolution_add_fusion.5 = f32[64,64,224,224] "
        "fusion(f32[64,64,3,3] %copy-done.14)")
    assert trace_reduce.op_class(fusion) == "convolution_add_fusion"
    assert trace_reduce.op_class("%copy-start = (f32[8], u32[]) "
                                 "copy-start(f32[8] %x)") == "copy-start"


def test_no_device_plane_reduces_to_nothing():
    assert trace_reduce.reduce({}, [("bench.window", 0, 10)]) is None
    assert trace_reduce.reduce({"/device:TPU:0": []}, []) is None


def test_recorded_chip_trace():
    """Three calls of two 4096x4096 fusions, each after a 20 ms host
    sleep, recorded on one TPU v5 lite chip."""
    r = trace_reduce.reduce_file(RECORDED)
    assert r is not None and r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.068972888)
    # six fusions of about 0.7 ms each, all inside the window
    assert r["busy_s"] == pytest.approx(0.004245458)
    assert r["idle_share"] == pytest.approx(1 - 0.004245458 / 0.068972888)
    classes = dict(r["op_classes"])
    assert set(classes) >= {"fusion", "convolution_tanh_fusion"}
    assert classes["fusion"] + classes["convolution_tanh_fusion"] == \
        pytest.approx(r["busy_s"], rel=1e-3)
    assert sum(s for _, s in r["idle_gaps"]) <= \
        r["window_s"] - r["busy_s"] + 1e-12
    # the three sleeps are the three longest gaps, each under its span
    sleeps = r["idle_gaps"][:3]
    assert [n for n, _ in sleeps] == ["bench.next_input"] * 3
    assert all(0.015 < s < 0.025 for _, s in sleeps)
