"""``core.tracing``: spans and counters inside the program, off by default.

Off, ``span``/``count`` record nothing and register no JAX listener.  On,
spans nest by ``parent``, JAX's compile phases arrive as ``jax.*`` spans
under the span that was open (nested phases merged), and a BCD outer step
on the suffix engine yields the documented span tree and per-step prefix
counts equal to the trie's own.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bcd, linearize, snl, tracing
from repro.data import ImageDatasetCfg, SyntheticImages
from repro.launch import sweep
from repro.models.resnet import CNN, CNNConfig
from repro.training import train as train_lib

_REGISTER = ("register_event_listener", "register_event_time_span_listener",
             "register_event_duration_secs_listener")


def test_off_records_nothing_and_registers_no_listener(monkeypatch):
    with tracing.recording() as rec:
        pass
    calls = []
    for name in _REGISTER:
        monkeypatch.setattr(jax.monitoring, name,
                            lambda *a, name=name, **k: calls.append(name))
    # one shared no-op context: nothing is allocated per span
    assert tracing.span("a") is tracing.span("b")
    with tracing.span("bcd.step"):
        with tracing.span("engine.stage"):
            tracing.count("engine.prefix_hits", 3)
            # a fresh jit compiled while off reaches no listener
            jax.jit(lambda x: x * 5.0 + 2.0)(jnp.ones(3)).block_until_ready()
    assert calls == []
    assert rec.spans == [] and not rec.counts and rec.step_counts == []


def test_nested_spans_carry_their_parent():
    with tracing.recording() as rec:
        with tracing.span("a"):
            with tracing.span("b"):
                with tracing.span("c"):
                    pass
            with tracing.span("d"):
                pass
        with tracing.span("e"):
            pass
    names = [s.name for s in rec.spans]
    assert names == ["a", "b", "c", "d", "e"]
    parent = {s.name: (None if s.parent is None else names[s.parent])
              for s in rec.spans}
    assert parent == {"a": None, "b": "a", "c": "b", "d": "a", "e": None}
    for s in rec.spans:
        assert s.t0 <= s.t1
        if s.parent is not None:
            p = rec.spans[s.parent]
            assert p.t0 <= s.t0 and s.t1 <= p.t1


def _jit_inside(x):
    """A jit defined inside a function: every call traces, lowers and
    compiles (or loads) it again; its body calls a second jit, whose trace
    nests inside this one's."""
    @jax.jit
    def step(x):
        return jnp.tanh(jax.jit(lambda y: y @ y)(x)).sum()
    return step(x)


def test_jit_inside_a_function_gives_one_span_per_phase_per_call():
    x = jnp.ones((8, 8))
    with tracing.recording() as rec:
        for _ in range(2):
            with tracing.span("outer"):
                _jit_inside(x).block_until_ready()
    outer = [i for i, s in enumerate(rec.spans) if s.name == "outer"]
    assert len(outer) == 2
    for i in outer:
        kids = [s for s in rec.spans if s.parent == i]
        assert sorted(s.name for s in kids) == \
            ["jax.compile", "jax.lower", "jax.trace"]
        p = rec.spans[i]
        for s in kids:
            assert "step" in s.fun_name
            # JAX's time.time() stamps land inside the parent on the
            # perf_counter clock
            assert p.t0 <= s.t0 <= s.t1 <= p.t1


def test_a_second_recording_inside_the_first_raises():
    with tracing.recording():
        with pytest.raises(RuntimeError, match="already on"):
            with tracing.recording():
                pass
    with tracing.recording() as rec:      # the first one ended cleanly
        tracing.count("x")
    assert rec.counts == {"x": 1}


def test_counts_are_kept_per_step():
    with tracing.recording() as rec:
        tracing.count("n")
        for k in range(2):
            with tracing.span(tracing.STEP):
                tracing.count("n", k + 1)
    assert rec.counts["n"] == 4
    assert [dict(c) for c in rec.step_counts] == [{"n": 1}, {"n": 2}]


@pytest.fixture(scope="module")
def tiny_bcd():
    """A tiny CNN whose only live ReLUs sit at its deepest site, so every
    candidate takes the suffix engine's path."""
    model = CNN(CNNConfig("tiny", 4, 16, ((8, 1, 1), (16, 1, 2)),
                          stem_channels=8))
    data = SyntheticImages(ImageDatasetCfg(n_classes=4, image_size=16,
                                           n_train=128, n_test=32))
    params = model.init(jax.random.PRNGKey(0))
    batch = data.train_eval_set(64)
    deep = model.site_order()[-1]
    masks0 = {k: np.asarray(v) * (k == deep)
              for k, v in linearize.init_masks(model.mask_sites()).items()}
    return model, params, batch, masks0


def test_bcd_steps_span_tree_and_prefix_counts(tiny_bcd):
    model, params, batch, masks0 = tiny_bcd
    holder = {"params": params}
    ev, eval_acc, set_ctx = sweep.make_bcd_evaluator(
        "suffix", model, batch, holder, chunk_size=4, rt=8)

    def sloss(p, a, b, soft):
        logits = model.forward(p, a, b["images"], soft=soft)
        return train_lib.cross_entropy(logits, b["labels"]), 0.0

    def finetune(masks):
        holder["params"] = snl.finetune(
            holder["params"], masks, sloss,
            lambda i: {k: v[:16] for k, v in batch.items()}, steps=2,
            lr=0.01)
        set_ctx(holder["params"])

    cfg = bcd.BCDConfig(b_target=0, drc=8, rt=8, adt=-1000.0,
                        chunk_size=4)
    gen = bcd.bcd_steps(bcd.init_state(masks0, cfg), cfg, eval_acc,
                        finetune, evaluator=ev)
    kinds = ("hits", "extensions", "misses", "evictions")
    deltas = []
    with tracing.recording() as rec:
        for _ in range(2):
            before = [getattr(ev.trie, k) for k in kinds]
            next(gen)
            deltas.append({k: getattr(ev.trie, k) - b
                           for k, b in zip(kinds, before)})
    gen.close()

    spans = rec.spans
    names = [s.name for s in spans]

    def ancestors(i):
        out = []
        while spans[i].parent is not None:
            i = spans[i].parent
            out.append(names[i])
        return out

    steps = [i for i, n in enumerate(names) if n == "bcd.step"]
    assert len(steps) == 2
    for i in steps:
        assert spans[i].parent is None
        kids = {names[j] for j, s in enumerate(spans) if s.parent == i}
        assert {"bcd.base_eval", "bcd.select", "snl.finetune",
                "bcd.post_eval"} <= kids
    for j, n in enumerate(names):
        if n.startswith("engine.") or n == "bcd.sample":
            assert "bcd.select" in ancestors(j), n
        if n == "engine.prefix":
            assert ancestors(j)[0] == "engine.stage"
    assert {"engine.stage", "engine.prefix", "engine.wait",
            "bcd.sample"} <= set(names)
    # the finetune re-jits its step on every call: its compile phases sit
    # inside snl.finetune
    for j, n in enumerate(names):
        if n.startswith("jax.") and spans[j].fun_name and \
                "step" in spans[j].fun_name:
            assert "snl.finetune" in [names[spans[j].parent]] + \
                ancestors(spans[j].parent)
    assert len(rec.step_counts) == 2
    for counts, delta in zip(rec.step_counts, deltas):
        assert delta["misses"] >= 1
        assert {k: counts.get("engine.prefix_" + k, 0) for k in kinds} == \
            delta
