"""The trace reduction, on hand-made events and on a small trace recorded
on one TPU v5e (``data/small.xplane.pb``: three windows of a jitted
``_tick_impl``, a second jitted program and an eager add, 2048x2048
bf16, each call wrapped in the harness's annotations)."""
import os

import pytest

from bench import tracing
from bench.tests.tiny import DATA

CATS = {"tick": "_tick_impl", "prefill": "_prefill_one_impl"}
MS = 1e6  # ns


def synthetic():
    """One device; window 0-100 ms on the host, device clock 1 ms early."""
    off = -1 * MS
    mods = [("jit__tick_impl(7)", 10 * MS + off, 20 * MS + off),
            ("jit__prefill_one_impl(9)", 40 * MS + off, 70 * MS + off),
            ("jit_scatter(3)", 80 * MS + off, 85 * MS + off)]
    ops = [("%fusion.1 = bf16[8] fusion()", 10 * MS + off, 15 * MS + off),
           ("%fusion.2 = bf16[8] fusion()", 14 * MS + off, 20 * MS + off),
           ("%dot.1 = bf16[8] dot()", 40 * MS + off, 70 * MS + off),
           ("%scatter.4 = bf16[8] scatter()", 80 * MS + off, 85 * MS + off)]
    host = [("bench.window", 0.0, 100 * MS),
            ("bench.step", 10 * MS - 0.1 * MS, 20 * MS),
            ("bench.wait", 20 * MS, 40 * MS),
            ("bench.admit", 40 * MS, 90 * MS)]
    return {"devices": [{"ops": ops, "modules": mods}], "host": host}


def test_busy_is_the_union_of_operations():
    r = tracing.reduce(synthetic(), CATS)
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx((10 + 30 + 5) / 1e3)


def test_device_time_by_module():
    m = tracing.reduce(synthetic(), CATS)["modules"]
    assert m["tick"] == {"device_s": pytest.approx(0.010), "count": 1}
    assert m["prefill"] == {"device_s": pytest.approx(0.030), "count": 1}
    assert m["other"] == {"device_s": pytest.approx(0.005), "count": 1}


def test_idle_gaps_are_named_by_the_host_span_over_them():
    gaps = tracing.reduce(synthetic(), CATS)["idle_gaps"]
    named = {round(s * 1e3, 3): n for n, s in gaps}
    # host spans move onto the device clock by -0.9 ms (first step at
    # 9.9 ms, first device operation at 9 ms); the window is -0.9..99.1
    assert named == {9.9: "host:unannotated", 20.0: "bench.wait",
                     10.0: "bench.admit", 15.1: "bench.admit"}
    assert [s for _, s in gaps] == sorted((s for _, s in gaps), reverse=True)


def test_top_operations_have_short_names():
    ops = dict(tracing.reduce(synthetic(), CATS)["device_ops"])
    assert ops["jit__prefill_one_impl/dot.1"] == pytest.approx(0.030)
    assert ops["jit__tick_impl/fusion.2"] == pytest.approx(0.006)


def test_no_device_plane_is_an_error():
    t = synthetic()
    t["devices"] = []
    with pytest.raises(ValueError):
        tracing.reduce(t, CATS)


@pytest.fixture(scope="module")
def recorded():
    return tracing.read_xspace(os.path.join(DATA, "small.xplane.pb"))


def test_recorded_trace_is_read(recorded):
    assert len(recorded["devices"]) == 1
    dev = recorded["devices"][0]
    assert len(dev["modules"]) == 9 and len(dev["ops"]) == 21
    names = [n for n, _, _ in recorded["host"]]
    assert names.count("bench.window") == 1
    assert names.count("bench.step") == 3


def test_recorded_trace_reduces_to_its_values(recorded):
    r = tracing.reduce(recorded, CATS)
    assert r["window_s"] == pytest.approx(0.162900854)
    assert r["busy_s"] == pytest.approx(0.000642356, rel=1e-6)
    assert r["modules"]["tick"]["count"] == 3
    assert r["modules"]["tick"]["device_s"] == pytest.approx(0.000295821,
                                                             rel=1e-6)
    assert r["modules"]["other"]["count"] == 6
    assert r["idle_gaps"][0][0] == "bench.admit"
    assert r["device_ops"][0][0] == "jit__tick_impl/convolution_tanh_fusion"
    assert r["busy_s"] <= r["window_s"]
