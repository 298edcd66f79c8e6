"""The trace reduction: busy/idle union, per-program device time and the
idle gaps' attribution, on hand-made events and on an excerpt recorded on a
TPU v5e (``data/trace_excerpt.json``)."""
import json
from pathlib import Path

import pytest

import bench_names  # noqa: F401  (puts benchmarks/chip on the path)
import devtrace
from devtrace import Event

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def test_hand_made_events():
    gather = "%fusion = s32[4096,20]{1,0:T(8,128)} fusion(s32[131045,20]{1,0} %copy), kind=kCustom"
    copy = "%copy.1 = f32[131045,20]{1,0:T(8,128)S(1)} copy(f32[131045,20]{0,1:T(8,128)} %p)"
    loop = "%while.2 = (s32[]{:T(128)}, f32[8,4]{1,0}) while((s32[], f32[8,4]) %t), body=%b"
    evs = [
        Event(HOST, "t", "bench:window", 0, 100),
        Event(HOST, "t", "bench:query_batch", 0, 15),
        Event(HOST, "t", "bench:readback", 15, 10),
        Event(HOST, "t", "bench:flush", 40, 50),
        Event(DEV, "XLA Modules", "jit_serve_gather(7)", 0, 15),
        Event(DEV, "XLA Modules", "jit_serve_gather(7)", 50, 10),
        Event(DEV, "XLA Modules", "jit__sweep_program(9)", 80, 30),   # clipped to 80..100
        Event(DEV, "XLA Ops", gather, 0, 10),
        Event(DEV, "XLA Ops", copy, 5, 10),
        Event(DEV, "XLA Ops", gather, 50, 10),
        Event(DEV, "XLA Ops", loop, 80, 30),       # control flow: not a leaf op
        Event(DEV, "XLA Ops", copy, 85, 5),
    ]
    s = devtrace.reduce(evs)
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx(45e-9)           # 0..15, 50..60, 80..100
    assert s.idle_share == pytest.approx(0.55)
    assert s.programs_s == pytest.approx({"jit_serve_gather": 25e-9,
                                          "jit__sweep_program": 20e-9})
    assert s.program_calls == {"jit_serve_gather": 2, "jit__sweep_program": 1}
    assert dict(s.device_ops) == pytest.approx({"fusion fusion s32[4096,20]": 20e-9,
                                                "copy.1 copy f32[131045,20]": 15e-9})
    # idle 15..50 (its middle, 32.5, in no span but the window), 60..80 (in flush)
    assert dict(s.idle_gaps) == pytest.approx({"other": 35e-9, "flush": 20e-9})


def test_op_label():
    assert devtrace.op_label(
        "%copy-start = (f32[131045,20]{0,1:T(8,128)S(1)}, u32[]{:S(2)}) copy-start(f32[1] %x)"
    ) == ("copy-start", "copy-start copy-start (f32[131045,20], u32[])")


def test_two_device_planes_average_their_busy_time():
    evs = [Event(HOST, "t", "bench:window", 0, 100),
           Event(DEV, "XLA Modules", "jit_a(1)", 0, 40),
           Event("/device:TPU:1", "XLA Modules", "jit_a(1)", 0, 20)]
    assert devtrace.reduce(evs).busy_s == pytest.approx(30e-9)


def test_no_device_op_is_an_error():
    with pytest.raises(ValueError):
        devtrace.reduce([Event(HOST, "t", "bench:window", 0, 100)])


EXCERPT = Path(__file__).parent / "data" / "trace_excerpt.json"


def test_recorded_excerpt_against_a_plain_sweep():
    evs = [Event(**e) for e in json.loads(EXCERPT.read_text())]
    win = next(e for e in evs if e.name == "bench:window")
    lo, hi = win.start_ns, min(win.start_ns + win.dur_ns,
                               max(e.start_ns + e.dur_ns for e in evs if devtrace.is_module(e)))
    s = devtrace.reduce(evs, (lo, hi))
    # busy by a plain boundary sweep over the program runs of plane 0
    ops = [(max(e.start_ns, lo), min(e.start_ns + e.dur_ns, hi)) for e in evs
           if devtrace.is_module(e) and e.plane == s_plane(evs)]
    points = sorted([(a, 1) for a, b in ops if b > a] + [(b, -1) for a, b in ops if b > a])
    busy, depth, last = 0.0, 0, None
    for t, step in points:
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    assert s.busy_s == pytest.approx(busy * 1e-9, rel=1e-9)
    mods = [e for e in evs if devtrace.is_module(e)]
    for name, sec in s.programs_s.items():
        want = sum(min(e.start_ns + e.dur_ns, hi) - max(e.start_ns, lo) for e in mods
                   if devtrace.program_name(e.name) == name
                   and min(e.start_ns + e.dur_ns, hi) > max(e.start_ns, lo))
        assert sec == pytest.approx(want * 1e-9, rel=1e-9)


def s_plane(evs):
    return sorted({e.plane for e in evs if devtrace.is_module(e)})[0]
