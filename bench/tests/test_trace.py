"""The reduction from a trace to busy time, idle share, collective time,
exposed share and named idle gaps, on a hand-built trace."""

from __future__ import annotations

import glob
import os

import jax
import jax.numpy as jnp
import pytest

from bench.lib import trace as tr

E = tr.Event


@pytest.fixture
def hand():
    """Two chips in a 100 ns window. Chip A: compute 0-10 and 30-40, an
    all-reduce 5-20 and a collective-permute 50-70, in two programs.
    Chip B: one op 0-50 outside any program. Host spans: admit 15-45,
    decode_step 65-90."""
    a = tr.Device(
        ops=[E(0, 10, "fusion.1"), E(5, 20, "all-reduce.1"), E(30, 40, "fusion.2"),
             E(50, 70, "collective-permute-start.3")],
        modules=[E(0, 45, "jit_step(1)"), E(50, 75, "jit_step(1)")],
    )
    b = tr.Device(ops=[E(0, 50, "fusion.1")], modules=[])
    return tr.Trace(window=(0.0, 100.0), devices={"/device:TPU:0": a, "/device:TPU:1": b},
                    spans=[E(15, 45, "admit"), E(65, 90, "decode_step")])


def test_busy_is_the_union_of_op_intervals(hand):
    a = hand.devices["/device:TPU:0"]
    assert tr.busy(a, hand.window) == [(0, 20), (30, 40), (50, 70)]
    # chip A busy 50 ns, chip B 50 ns: mean 50 ns of a 100 ns window
    assert tr.busy_s(hand) == pytest.approx(50e-9)
    assert tr.idle_share(hand) == pytest.approx(0.5)


def test_collective_time_and_exposed_part(hand):
    total, exposed = tr.collective_split(hand.devices["/device:TPU:0"], hand.window)
    # all-reduce 5-20 (15) + permute 50-70 (20); compute covers only 5-10
    assert total == pytest.approx(35)
    assert exposed == pytest.approx(30)
    assert tr.collective_split(hand.devices["/device:TPU:1"], hand.window) == (0, 0)


def test_async_collectives_count_from_start_to_done(hand):
    a = hand.devices["/device:TPU:0"]
    with_async = tr.Device(ops=a.ops, modules=a.modules,
                           async_ops=[E(72, 95, "all-gather-start.4"), E(0, 3, "copy-start.1")])
    total, exposed = tr.collective_split(with_async, hand.window)
    # adds 72-95 (23 ns, nothing else runs); the async copy is no collective
    assert total == pytest.approx(58) and exposed == pytest.approx(53)


def test_idle_gaps_are_named_by_the_host_span(hand):
    gaps = tr.idle_gaps(hand)
    assert [g[0] for g in gaps] == ["decode_step", "admit", "admit"]
    assert [g[1] for g in gaps] == pytest.approx([30e-9, 10e-9, 10e-9])


def test_device_time_inside_spans(hand):
    # decode_step 65-90 holds chip A's program 50-75 for 10 ns; chip B none
    assert tr.device_time_in_spans(hand, "decode_step") == (pytest.approx(5.0), 1)
    assert tr.device_time_in_spans(hand, "train_step") == (0.0, 0)


def test_op_seconds_by_program_and_kind(hand):
    got = dict((k, v) for k, v in tr.op_seconds(hand))
    assert got == pytest.approx({
        "?/fusion": 25e-9, "jit_step/fusion": 10e-9,
        "jit_step/collective-permute-start": 10e-9, "jit_step/all-reduce": 7.5e-9,
    })


def test_window_clips_every_reduction(hand):
    clipped = tr.Trace(window=(5.0, 35.0), devices=hand.devices, spans=hand.spans)
    # chip A busy 5-20, 30-35 = 20; chip B 5-35 = 30
    assert tr.busy_s(clipped) == pytest.approx(25e-9)


def test_names():
    assert tr.op_name("%fusion.151 = s32[16]{0} fusion(s32[16]{0} %x), kind=kLoop") == "fusion.151"
    assert tr.op_kind("all-reduce-start.3") == "all-reduce-start"
    assert tr.module_name("jit_serve_step(3144510844975366864)") == "jit_serve_step"
    assert tr.is_collective("collective-permute-done.2")
    assert not tr.is_collective("fusion.2")


def test_load_reads_the_benchmark_spans(tmp_path):
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.traced"):
        with jax.profiler.TraceAnnotation("bench.admit"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    assert glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"), recursive=True)
    t = tr.load(str(tmp_path))
    assert t.window[1] > t.window[0]
    assert [s.name for s in t.spans] == ["admit"]
    assert t.window[0] <= t.spans[0].start <= t.spans[0].end <= t.window[1]
