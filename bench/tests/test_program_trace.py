"""The program's spans and JAX's compile phases on the profiler's clock
(``bench/lib/program_trace.py``) and the three metrics that read them, on
hand-made traces and on one recorded on a TPU v5e chip
(``data/program_trace.xplane.pb.gz``: two ``bcd.step`` spans of the
program inside a ``bench.window`` span, the first compiling a fresh jit
inside ``snl.finetune``, each waiting on a matmul inside ``engine.wait``;
``data/program_trace.json``: the program's recorder of that run and the
window's two ends on ``time.perf_counter``)."""
import gzip
import json
import os
import types

import pytest

from bench.lib import program_trace as pt
from bench.lib import spec, trace
from bench.lib.trace import Event, Trace
from conftest import ROOT

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _reader(name):
    return spec.Spec.load(ROOT).reader(name)


def _hand_made():
    """Window [0, 10] on the profiler's clock; the recorder's clock reads
    100 s more.  Device busy [0, 1] and [4.5, 5].  One step: the finetune
    [0.5, 6] holds a trace [1, 2] (with a nested trace [1.2, 1.5]), a
    lowering [2, 2.5] and a compile [2.5, 4]; a second step holds one more
    compile [7, 7.5], and a compile [10.5, 11] falls after the window."""
    ops = [Event("fusion", 0, 1), Event("fusion", 4.5, 5)]
    t = Trace((0.0, 10.0), [ops], [])
    spans = [("bcd.step", 100.0, 106.5, None),
             ("snl.finetune", 100.5, 106.0, 0),
             ("jax.trace", 101.0, 102.0, 1), ("jax.trace", 101.2, 101.5, 1),
             ("jax.lower", 102.0, 102.5, 1), ("jax.compile", 102.5, 104.0, 1),
             ("bcd.step", 106.5, 110.0, None),
             ("jax.compile", 107.0, 107.5, 6),
             ("jax.compile", 110.5, 111.0, 6)]
    events = pt.to_profiler_clock(spans, (100.0, 110.0), t.window)
    return t, events


def _reading(t, events, n_steps=2, trie=None):
    return types.SimpleNamespace(
        trace=t, program_events=events, steps=[{}] * n_steps,
        window_s=t.window_s,
        job=types.SimpleNamespace(engine=types.SimpleNamespace(trie=trie)))


def test_clock_mapping_and_its_anchor_check():
    t, events = _hand_made()
    assert events[0] == Event("bcd.step", 0.0, 6.5)
    # half a millisecond of drift passes; two do not
    pt.to_profiler_clock([("a", 1.0, 2.0)], (0.0, 10.0), (5.0, 15.0005))
    with pytest.raises(ValueError, match="disagree by 2.000 ms"):
        pt.to_profiler_clock([("a", 1.0, 2.0)], (0.0, 10.0), (5.0, 15.002))


def test_compile_seconds_per_step():
    t, events = _hand_made()
    # union in the window: [1, 4] and [7, 7.5]; the nested trace and the
    # compile after the window do not count
    r = _reading(t, events)
    assert _reader("bcd.compile_s_per_step").read(r) == pytest.approx(1.75)
    assert _reader("bcd.compile_s_per_step").read(
        _reading(t, None)) is None
    no_phase = [e for e in events if not pt.is_jax(e.name)]
    assert _reader("bcd.compile_s_per_step").read(
        _reading(t, no_phase)) == 0.0


def test_idle_by_program_span_and_the_compile_share():
    t, events = _hand_made()
    idle = pt.idle_by_program_span(t, events)
    assert idle == pytest.approx({
        "snl.finetune": 0.5 + 1.0, "jax.trace": 1.0, "jax.lower": 0.5,
        "jax.compile": 1.5 + 0.5, "bcd.step": 0.5 + 0.5 + 2.5})
    assert sum(idle.values()) == pytest.approx(t.window_s - trace.busy_s(t))
    share = _reader("device.idle_share.compile").read(_reading(t, events))
    assert share == pytest.approx(100.0 * 3.5 / 10.0)
    # no program span over a stretch: it is named as such
    bare = pt.idle_by_program_span(t, [Event("jax.compile", 2.0, 3.0)])
    assert bare == pytest.approx({"jax.compile": 1.0, pt.NO_SPAN: 7.5})


def test_self_times_leave_out_what_children_cover():
    t, events = _hand_made()
    self_s = pt.self_times(events, *t.window)
    assert self_s == pytest.approx({
        "bcd.step": 1.0 + 3.0, "snl.finetune": 2.5, "jax.trace": 1.0,
        "jax.lower": 0.5, "jax.compile": 2.0})
    assert sum(self_s.values()) == pytest.approx(t.window_s)


def test_prefix_reuse_share_from_the_trie():
    t, events = _hand_made()
    read = _reader("engine.prefix_reuse_share").read
    trie = types.SimpleNamespace(hits=6, extensions=2, misses=2)
    assert read(_reading(t, events, trie=trie)) == pytest.approx(80.0)
    none = types.SimpleNamespace(hits=0, extensions=0, misses=0)
    assert read(_reading(t, events, trie=none)) is None
    assert read(_reading(t, events, trie=None)) is None


def test_recorded_v5e_program_trace(tmp_path):
    """The recorder's spans, moved onto the profiler's clock by the
    ``bench.window`` anchors, land within 1 ms of their own ``repro.*``
    annotations in the chip's trace, and the idle time on the device is
    all put down to some span."""
    path = tmp_path / "program_trace.xplane.pb"
    with gzip.open(os.path.join(DATA, "program_trace.xplane.pb.gz")) as f:
        path.write_bytes(f.read())
    with open(os.path.join(DATA, "program_trace.json")) as f:
        rec = json.load(f)
    t = trace.load(str(path))
    events = pt.host_events(str(path))
    mapped = pt.to_profiler_clock(rec["spans"], rec["anchors"], t.window)
    program = sorted((e for e in events if not pt.is_jax(e.name)),
                     key=lambda e: e.start)
    ours = sorted((e for e in mapped if not pt.is_jax(e.name)),
                  key=lambda e: e.start)
    assert [e.name for e in ours] == [e.name for e in program] == [
        "bcd.step", "snl.finetune", "engine.wait"] * 2
    for a, b in zip(ours, program):
        assert abs(a.start - b.start) < 1e-3 and abs(a.end - b.end) < 1e-3
    # the jit traced inside the first finetune: nested jax.trace events in
    # the trace, one merged span in the recorder, on the same interval
    xs = [e for e in events if e.name == "jax.trace"]
    (y,) = [e for e in mapped if e.name == "jax.trace"]
    assert len(xs) > 1
    assert abs(min(e.start for e in xs) - y.start) < 1e-3
    assert abs(max(e.end for e in xs) - y.end) < 1e-3
    idle = pt.idle_by_program_span(t, events)
    assert sum(idle.values()) == pytest.approx(t.window_s - trace.busy_s(t))
    # but for 0.5 us: the device's clock reads about 1 ms behind the
    # host's here, so the matmul dispatched after the trace shows inside it
    assert idle["jax.trace"] == pytest.approx(pt.union_s(xs, *t.window),
                                              abs=1e-5)
    assert set(idle) <= {"bcd.step", "snl.finetune", "engine.wait",
                         "jax.trace", pt.NO_SPAN}
