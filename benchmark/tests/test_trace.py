"""The trace reduction on a synthetic trace, and on a real one recorded
here on the CPU (host annotations only: no device plane)."""

import pytest

from lib import trace
from lib.trace import Event

DEV = "/device:TPU:0"


def ev(plane, line, name, start, dur):
    return Event(plane, line, name, float(start), float(dur))


def synthetic():
    return [
        ev("/host:CPU", "client-0", "bench.put", 0, 1000),
        ev("/host:CPU", "client-1", "bench.get", 400, 700),
        ev(DEV, "XLA Ops", "fusion.1", 100, 100),   # 100..200
        ev(DEV, "XLA Ops", "fusion.2", 150, 100),   # overlaps: 100..250
        ev(DEV, "XLA Ops", "infeed.3", 600, 50),  # a host transfer
        ev(DEV, "XLA Ops", "fusion.1", 1150, 100),  # after the window
        ev(DEV, "XLA Modules", "jit_f", 100, 600),
        ev(DEV, "Steps", "0", 0, 2000),
    ]


def test_busy_kernel_and_gaps():
    s = trace.summarize(synthetic())
    assert s.window_s == pytest.approx(1100e-9)
    assert s.busy_s == pytest.approx(200e-9)  # 100..250 and 600..650
    assert s.kernel_s == pytest.approx(150e-9)
    assert s.transfer_s == pytest.approx(50e-9)
    assert s.chips == 1
    names = dict(s.device_ops)
    assert names["fusion.1"] == pytest.approx(100e-9)
    # gaps 650..1100, 250..600 and 0..100, labelled at their midpoints
    assert s.idle_gaps == [["get*1+put*1 | python", pytest.approx(450e-9)],
                           ["get*1+put*1 | python", pytest.approx(350e-9)],
                           ["put*1 | python", pytest.approx(100e-9)]]
    assert s.unmatched_s == pytest.approx(400e-9)  # module 100..700 less busy


def test_short_names():
    hlo = ('%f.1 = u32[4,262144]{1,0:T(4,128)} custom-call(u32[10,262144]'
           '{1,0:T(8,128)} %words.1), custom_call_target="tpu_custom_call"')
    assert trace.short_name(hlo) == "custom-call:tpu_custom_call u32[4,262144]"
    assert trace.short_name("%copy = u32[2,10,8192]{2,1,0:T(8,128)S(1)} "
                            "copy(u32[2,10,8192]{2,0,1:T(2,128)} %w)") == (
        "copy u32[2,10,8192]")
    assert trace.short_name("fusion.3") == "fusion.3"


def test_no_annotation_is_an_error():
    with pytest.raises(ValueError):
        trace.summarize([ev(DEV, "XLA Ops", "f", 0, 10)])


def test_reads_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.put"):
        (jnp.ones((64, 64)) @ jnp.ones((64, 64))).block_until_ready()
    jax.profiler.stop_trace()
    path = next(tmp_path.glob("**/*.xplane.pb"))
    events = trace.read_events(str(path))
    s = trace.summarize(events)
    assert s.window_s > 0 and s.busy_s == 0 and s.chips == 0
    assert "/host:CPU" in trace.shape(events)
