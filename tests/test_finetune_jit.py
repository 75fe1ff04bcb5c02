"""``snl.finetune`` builds its jitted step once per (loss function, lr,
steps, optimizer): repeat calls reuse it, compute what a step jitted
inline computes, and a changed key never reuses a stale program."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bcd, linearize, masks as M, snl, tracing
from repro.data import ImageDatasetCfg, SyntheticImages
from repro.launch import sweep
from repro.models.resnet import CNN, CNNConfig
from repro.training import optimizer as opt_lib, train as train_lib

JAX_PHASES = ("jax.trace", "jax.lower", "jax.compile")


@pytest.fixture(scope="module")
def tiny():
    model = CNN(CNNConfig("tiny", 4, 16, ((8, 1, 1), (16, 1, 2)),
                          stem_channels=8))
    data = SyntheticImages(ImageDatasetCfg(n_classes=4, image_size=16,
                                           n_train=128, n_test=32))
    params = model.init(jax.random.PRNGKey(0))
    batch = {k: jnp.asarray(v) for k, v in data.train_eval_set(64).items()}
    sites = model.mask_sites()
    rng = np.random.default_rng(0)
    total = sum(int(np.prod(s.shape)) for s in sites.values())
    masks = M.threshold({k: rng.random(s.shape).astype(np.float32)
                         for k, s in sites.items()}, total // 2)
    # four distinct batches of 16, already on the device
    slices = [{k: v[16 * i:16 * (i + 1)] for k, v in batch.items()}
              for i in range(4)]
    return model, params, masks, lambda i: slices[i % 4]


def _loss(model):
    def sloss(p, a, b, soft):
        logits = model.forward(p, a, b["images"], soft=soft)
        return train_lib.cross_entropy(logits, b["labels"]), 0.0
    return sloss


class _Net:
    def __init__(self, model):
        self.model = model

    def loss(self, p, a, b, soft):
        logits = self.model.forward(p, a, b["images"], soft=soft)
        return train_lib.cross_entropy(logits, b["labels"]), 0.0


def _inline_finetune(params, masks, loss_fn, batches, *, steps, lr,
                     start_step=0, use_adam=False):
    """``snl.finetune`` as it was built before its step was hoisted: a new
    optimizer and a new ``@jax.jit`` step on every call."""
    opt = (opt_lib.adamw(lr=lr, schedule=opt_lib.cosine(lr, steps))
           if use_adam else
           opt_lib.sgd(lr=lr, momentum=0.9,
                       schedule=opt_lib.cosine(lr, steps)))
    masks_dev = M.as_device(masks)

    @jax.jit
    def step(p, ostate, batch, masks):
        def l(p):
            loss, _ = loss_fn(p, masks, batch, False)
            return loss
        grads = jax.grad(l)(p)
        updates, ostate = opt.update(grads, ostate, p)
        return opt_lib.apply_updates(p, updates), ostate

    ostate = opt.init(params)
    for i in range(steps):
        params, ostate = step(params, ostate, batches(start_step + i),
                              masks_dev)
    return params


def _leaves(tree):
    return [np.asarray(x) for x in jax.tree.leaves(tree)]


def _bitwise_equal(a, b):
    return all(x.dtype == y.dtype and x.shape == y.shape and
               np.array_equal(x, y) for x, y in zip(_leaves(a), _leaves(b)))


@pytest.mark.parametrize("kind", ["function", "bound_method"])
def test_second_call_traces_nothing(tiny, kind):
    model, params, masks, batches = tiny
    # a loss function object this process has not seen yet; a bound
    # method is a new object on every access, equal to the last one
    net, fn = _Net(model), _loss(model)
    loss = (lambda: fn) if kind == "function" else (lambda: net.loss)
    with tracing.recording() as rec:
        p1 = snl.finetune(params, masks, loss(), batches, steps=3, lr=0.01)
        first = rec.counts["snl.finetune_trace"]
        i2 = len(rec.spans)
        p2 = snl.finetune(p1, masks, loss(), batches, steps=3, lr=0.01,
                          start_step=3)
    assert 1 <= first <= 2
    assert rec.counts["snl.finetune_trace"] == first
    firsts = [s for s in rec.spans[:i2] if s.name in JAX_PHASES
              and s.fun_name and "_finetune_step" in s.fun_name]
    assert {s.name for s in firsts} >= {"jax.trace", "jax.lower"}
    # the second call: its own span and no jax.trace/lower/compile
    assert [s.name for s in rec.spans[i2:]] == ["snl.finetune"]
    assert not _bitwise_equal(p2, p1)


@pytest.mark.parametrize("use_adam", [False, True], ids=["sgd", "adamw"])
def test_hoisted_step_matches_inline_jit_bitwise(tiny, use_adam):
    model, params, masks, batches = tiny
    loss_fn = _loss(model)
    kw = dict(steps=3, lr=0.03, start_step=1, use_adam=use_adam)
    got = snl.finetune(params, masks, loss_fn, batches, **kw)
    want = _inline_finetune(params, masks, loss_fn, batches, **kw)
    assert not _bitwise_equal(got, params)
    assert _bitwise_equal(got, want)
    # the same again, now from JAX's in-memory cache
    again = snl.finetune(params, masks, loss_fn, batches, **kw)
    assert _bitwise_equal(again, want)


@pytest.mark.parametrize("change", [{"lr": 0.05}, {"steps": 2},
                                    {"use_adam": True}],
                         ids=["lr", "steps", "use_adam"])
def test_changed_key_builds_a_new_program(tiny, change):
    model, params, masks, batches = tiny
    loss_fn = _loss(model)
    base = dict(steps=3, lr=0.01, use_adam=False)
    first = snl.finetune(params, masks, loss_fn, batches, **base)
    kw = {**base, **change}
    with tracing.recording() as rec:
        got = snl.finetune(params, masks, loss_fn, batches, **kw)
    assert rec.counts["snl.finetune_trace"] >= 1
    assert not _bitwise_equal(got, first)
    assert _bitwise_equal(
        got, _inline_finetune(params, masks, loss_fn, batches, **kw))


def test_bcd_run_traces_the_finetune_step_once(tiny):
    """A BCD run with one loss function traces the finetune's step in its
    first outer step and never again."""
    model, params, _, batches = tiny
    batch = {k: jnp.concatenate([batches(i)[k] for i in range(4)])
             for k in ("images", "labels")}
    deep = model.site_order()[-1]
    masks0 = {k: np.asarray(v) * (k == deep)
              for k, v in linearize.init_masks(model.mask_sites()).items()}
    holder = {"params": params}
    ev, eval_acc, set_ctx = sweep.make_bcd_evaluator(
        "suffix", model, batch, holder, chunk_size=4, rt=8)
    sloss = _loss(model)

    def finetune(masks):
        holder["params"] = snl.finetune(holder["params"], masks, sloss,
                                        batches, steps=2, lr=0.01)
        set_ctx(holder["params"])

    cfg = bcd.BCDConfig(b_target=0, drc=8, rt=8, adt=-1000.0,
                        chunk_size=4)
    gen = bcd.bcd_steps(bcd.init_state(masks0, cfg), cfg, eval_acc,
                        finetune, evaluator=ev)
    with tracing.recording() as rec:
        for _ in range(3):
            next(gen)
    gen.close()
    per_step = [c.get("snl.finetune_trace", 0) for c in rec.step_counts]
    assert len(per_step) == 3
    assert 1 <= per_step[0] <= 2
    assert per_step[1:] == [0, 0]
