"""The reader of the program's spans and scopes: self time of nested ops,
device time by scope, the five per-layer metrics on hand-built spans,
the refusal of a trace from another window, and the HLO read off a
recorded trace's metadata plane."""

from __future__ import annotations

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import pytest

from bench.lib import program_trace as pt
from bench.lib import record
from bench.lib import trace as tr

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
S = pt.Span
E = tr.Event


def test_self_time_of_a_while_excludes_its_body():
    # a while 0-100 holding ops 10-20 and 30-50, which holds 35-40; then 60-70
    events = [(0, 100), (10, 20), (30, 50), (35, 40), (60, 70), (120, 130)]
    own = pt.self_times(events)
    assert own[0] == [(0, 10), (20, 30), (50, 60), (70, 100)]
    assert own[2] == [(30, 35), (40, 50)]
    assert own[1] == [(10, 20)] and own[3] == [(35, 40)] and own[5] == [(120, 130)]
    # self times partition the busy time: nothing is counted twice
    assert sum(tr.length(o) for o in own) == tr.length(tr.merge(events))


def test_scope_paths_drop_autodiff_wrappers():
    path = pt.scope_path("jit(train_step)/grad_reduce/shard_map/chain/ppermute")
    assert pt.in_scope(path, "grad_reduce/chain")
    assert pt.in_scope(path, "grad_reduce")
    assert not pt.in_scope(path, "chain/grad_reduce")
    assert not pt.in_scope(path, "grad_reduce/optimizer")
    back = pt.scope_path("jit(train_step)/shard_map/transpose(jvp(attn))/dot_general")
    assert pt.in_scope(back, "attn") and not pt.in_scope(back, "mlp")
    assert not pt.in_scope(pt.scope_path("jit(step)/attention_mask/add"), "attn")


def _op(scope: str, *intervals) -> pt.Op:
    return pt.Op(pt.scope_path(scope), tuple(intervals))


@pytest.fixture
def program():
    """Two chips, a 0-200 ns window, two training steps and three
    admissions (one before the window)."""
    a = [_op("jit(s)/grad_reduce/shard_map/concatenate", (0, 10)),
         _op("jit(s)/grad_reduce/shard_map/chain/ppermute", (5, 30)),  # async
         _op("jit(s)/shard_map/attn/dot", (20, 40)),
         _op("jit(s)/grad_reduce/shard_map/slice", (150, 160), (170, 175)),
         _op("jit(s)/grad_reduce/shard_map/chain/ppermute", (190, 240))]  # past the window
    b = [_op("jit(s)/grad_reduce/shard_map/chain/ppermute", (0, 40)),
         _op("jit(s)/optimizer/add", (50, 60))]
    spans = [
        S("train.step", 0, 90, {}), S("train.sync", 60, 85, {}),
        S("train.batch", 2, 4, {}),
        S("train.step", 100, 190, {}), S("train.sync", 110, 180, {}),
        S("serve.admit_request", -50, -10,
          {"prompt_tokens": 100, "cached_tokens": 0, "queued_us": 9000}),
        S("serve.admit_request", 10, 50,
          {"prompt_tokens": 600, "cached_tokens": 512, "queued_us": 3000}),
        S("serve.admit_request", 120, 160,
          {"prompt_tokens": 200, "cached_tokens": 0, "queued_us": 1000}),
    ]
    return pt.ProgramTrace(window=(0.0, 200.0), spans=spans,
                           chips={"/device:TPU:0": a, "/device:TPU:1": b},
                           compiles=[E(92, 99, "backend_compile_and_load")])


def test_scoped_time_is_the_union_per_chip(program):
    # chip A: 0-30 (the concatenate and the permute overlap), 150-160, 170-175 and
    # 190-200 (clipped): 55 ns; chip B: 0-40. Mean 47.5 ns.
    assert pt.scoped_ns(program, "grad_reduce") == pytest.approx(47.5)
    assert pt.scoped_ns(program, "grad_reduce/chain") == pytest.approx((25 + 10 + 40) / 2)
    assert pt.scoped_ns(program, "attn") == pytest.approx(20 / 2)
    assert pt.scoped_ns(program, "optimizer") == pytest.approx(5)


def _run(program, trace=None):
    trace = trace or tr.Trace(window=program.window, devices={}, spans=[])
    return record.RunData(peaks={}, chips=2, spans=[], traced=(0.0, 1.0), trace=trace, facts={})


def _reader(name):
    return record.reader(ROOT, name)


def test_the_five_readers(program, monkeypatch):
    monkeypatch.setattr(pt, "load", lambda run: program)
    devices = {
        "/device:TPU:0": tr.Device(ops=[], modules=[E(0, 30, "jit_s(1)"), E(125, 130, "jit_s(1)")]),
        "/device:TPU:1": tr.Device(ops=[], modules=[E(20, 60, "jit_s(1)")]),
    }
    run = _run(program, tr.Trace(window=program.window, devices=devices, spans=[]))
    # two admissions in the window: queued 3000 and 1000 us
    assert _reader("serve.queue_wait_ms")(run) == pytest.approx(2.0)
    assert _reader("serve.prefix_token_share")(run) == pytest.approx(100 * 512 / 800)
    # 10-50: chip A 20 ns, chip B 30 ns -> 25; 120-160: A 5, B 0 -> 2.5
    assert _reader("serve.admit_device_ms")(run) == pytest.approx((25 + 2.5) / 2 * 1e-6)
    assert _reader("train.grad_reduce_ms")(run) == pytest.approx(47.5 / 2 * 1e-6)
    # steps of 90 ns less syncs of 25 and 70 ns
    assert _reader("train.step_host_ms")(run) == pytest.approx((65 + 20) / 2 * 1e-6)


def test_readers_find_nothing_without_program_spans(monkeypatch):
    """A trace of a program that writes no spans (an older version of
    it) gives no reading and raises nothing."""
    empty = pt.ProgramTrace(window=(0.0, 200.0), spans=[], chips={}, compiles=[])
    for found in (empty, None):
        monkeypatch.setattr(pt, "load", lambda run, found=found: found)
        for name in ("serve.queue_wait_ms", "serve.prefix_token_share", "serve.admit_device_ms",
                     "train.grad_reduce_ms", "train.step_host_ms"):
            assert _reader(name)(_run(empty)) is None, name


def test_gaps_are_named_by_the_innermost_span_or_compile(program):
    devices = {"/device:TPU:0": tr.Device(ops=[E(0, 50, "fusion.1"), E(100, 200, "fusion.2")],
                                          modules=[])}
    trace = tr.Trace(window=program.window, devices=devices, spans=[])
    # the one gap, 50-100: its middle, 75, lies in train.sync 60-85
    assert pt.idle_gaps(program, trace) == [["train.sync", pytest.approx(50e-9)]]
    assert pt.open_at(program, 95) == "compile"
    assert pt.open_at(program, 3) == "train.batch"
    assert pt.open_at(program, 195) == "none"


@jax.jit
def _scoped(x):
    with jax.named_scope("toy"):
        return jnp.sin(x @ x)


def _record(log_dir, counts):
    """A profiler session over one call of ``_scoped``, with the
    benchmark's window span and one program span, then one compile."""
    x = jnp.ones((16, 16))
    _scoped(x).block_until_ready()  # compiled before the session, as in a run
    jax.profiler.start_trace(str(log_dir))
    with jax.profiler.TraceAnnotation("bench.traced"):
        with jax.profiler.TraceAnnotation("repro.toy.call", **counts):
            _scoped(x).block_until_ready()
        jax.jit(lambda y: y * 3)(x).block_until_ready()
    jax.profiler.stop_trace()


def test_load_reads_spans_and_hlo_and_refuses_another_window(tmp_path):
    _record(tmp_path, {"rid": 7, "tokens": 3})
    got = pt.read(tr.xplane_file(str(tmp_path)))
    assert [(s.name, s.counts) for s in got.spans] == [("toy.call", {"rid": 7, "tokens": 3})]
    assert got.compiles and pt.open_at(got, got.compiles[0].start) == "compile"
    raw = open(tr.xplane_file(str(tmp_path)), "rb").read()
    programs = pt.programs_hlo(raw)
    scoped = [p for name, p in programs.items() if name.startswith("jit__scoped(")]
    assert scoped and any(pt.in_scope(pt.scope_path(n), "toy") for n in scoped[0].values())

    chip = {"/device:TPU:0": tr.Device(ops=[], modules=[])}
    same = _run(got, tr.Trace(window=got.window, devices=chip, spans=[]))
    first = pt.load(same, str(tmp_path))
    assert first is not None and pt.load(same, str(tmp_path)) is first  # parsed once
    stale = _run(got, tr.Trace(window=(got.window[0], got.window[1] + 1), devices=chip, spans=[]))
    assert pt.load(stale, str(tmp_path)) is None
    assert pt.load(same, str(tmp_path / "missing")) is None
    # a rehearsal on the CPU traces no chip: nothing is read
    assert pt.load(_run(got, tr.Trace(window=got.window, devices={}, spans=[])), str(tmp_path)) is None


TPU_TRACE = os.path.join(os.path.dirname(__file__), "data", "tpu_toy.xplane.pb")


def test_recorded_tpu_trace():
    """A v5e trace (20 KB) of a jitted function: a matmul under the
    ``toy`` scope, then a four-step loop under ``toy/inner`` whose body
    is one matmul fusion, then an add outside the scope; called once
    inside a ``repro.toy.call`` span."""
    got = pt.read(TPU_TRACE)
    assert [(s.name, s.counts) for s in got.spans] == [("toy.call", {"rid": 3, "tokens": 17})]
    assert list(got.chips) == ["/device:TPU:0"]
    # the device's clock puts its ops a millisecond before the host span
    # that ran them, so the window is widened to hold them
    wide = dataclasses.replace(got, window=(0.0, float("inf")))
    # the loop's four body fusions (35,513, 35,513, 35,512, 35,513 ns)
    # and the loop's own 236 ns; the while's 146,337 ns are not counted
    # again beside its body
    assert pt.scoped_ns(wide, "toy/inner") == 142_287
    assert pt.scoped_ns(wide, "toy") == 142_287 + 11_058  # and the first matmul
    assert pt.scoped_ns(wide, "add") == 6_807
    # XLA's copies carry no op_name: unscoped
    assert pt.scoped_ns(wide, "f") == 142_287 + 11_058 + 6_807


def test_analysis_prints_scopes_and_gaps(tmp_path, capsys):
    _record(tmp_path, {})
    pt.main([str(tmp_path), "toy", "add"])
    got = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert set(got) == {"window_s", "busy_s", "scope_s", "idle_gaps"}
    assert got["window_s"] > 0 and set(got["scope_s"]) == {"toy", "add"}
    # a CPU run traces no chip: no device time and no gaps
    assert got["busy_s"] == 0 and got["idle_gaps"] == []
