"""The trace reduction, on a hand-made trace and on one recorded on a TPU
v5e chip (``data/small_trace.xplane.pb``: three ``bench.work`` host spans
inside a ``bench.window`` span, each running a Pallas masked-activation
kernel and an XLA matmul; numbers below worked out by hand from its
events)."""
import os

import pytest

from bench.lib import spec, trace
from bench.lib.trace import Event, Trace
from conftest import ROOT

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _hand_made():
    # window [0, 10]; device ops [1,3] and [2,4] overlap, [6,7], and one
    # [9,12] that the window clips to [9,10]: busy 3 + 1 + 1 = 5
    ops = [Event("fusion.1", 1, 3), Event("_masked_act_kernel", 2, 4),
           Event("_masked_act_kernel", 6, 7), Event("convolution", 9, 12)]
    host = [Event("step", 0, 10), Event("engine.stage", 4.5, 5.5),
            Event("finetune", 7, 8.5)]
    return Trace((0.0, 10.0), [ops], host)


def test_busy_and_idle_share_of_a_hand_made_trace():
    t = _hand_made()
    assert trace.busy_s(t) == pytest.approx(5.0)
    assert trace.idle_share(t) == pytest.approx(0.5)


def test_kernel_time_by_name():
    t = _hand_made()
    assert trace.kernel_s(t, "masked_act") == pytest.approx(3.0)
    assert trace.kernel_s(t, "no_such_op") == 0.0


def test_idle_gaps_are_named_by_the_innermost_host_span():
    # gaps: [0,1] step, [4,6] engine.stage (midpoint 5), [7,9] finetune
    # (midpoint 8)
    gaps = dict(trace.idle_gaps(_hand_made()))
    assert gaps == pytest.approx({"step": 1.0, "engine.stage": 2.0,
                                  "finetune": 2.0})


def test_top_ops_rank_device_seconds():
    top = trace.top_ops(_hand_made())
    assert top[0] == ["_masked_act_kernel", pytest.approx(3.0)]
    assert [n for n, _ in top] == ["_masked_act_kernel", "fusion.1",
                                   "convolution"]


def test_recorded_v5e_trace():
    # window: bench.window from 47,660,678 ns for 11,980,720 ns.  Device
    # ops (XLA Ops line of /device:TPU:0), start and duration in ns; the
    # first iteration's three ops (46,723,677 + 2,368; 46,726,046 + 1,802;
    # 47,449,962 + 9,486) end before the window opens (the device clock
    # reads about 1 ms behind the host's here) and do not count.  Inside:
    #   50,928,355 + 2,302  Pallas masked-act kernel (tpu_custom_call)
    #   50,930,657 + 1,806  fusion
    #   51,665,668 + 9,505  fusion (matmul)
    #   55,204,642 + 2,166  Pallas masked-act kernel
    #   55,206,809 + 1,880  fusion
    #   55,787,592 + 9,587  fusion (matmul)
    # no two overlap: busy = 27,246 ns, kernel = 2,302 + 2,166 = 4,468 ns,
    # idle share = 1 - 27,246 / 11,980,720.
    t = trace.load(os.path.join(DATA, "small_trace.xplane.pb"))
    assert t.window_s == pytest.approx(11_980_720e-9)
    assert trace.busy_s(t) == pytest.approx(27_246e-9)
    assert trace.idle_share(t) == pytest.approx(1 - 27_246 / 11_980_720)
    pallas = spec.Spec.load(ROOT).reader("kernel.pallas_s_per_step")
    assert trace.kernel_s(t, pallas.PATTERN) == pytest.approx(4_468e-9)
    assert sorted(e.name for e in t.host_spans) == ["work"] * 3
