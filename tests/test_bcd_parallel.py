"""Candidate-evaluation engine: backend equivalence + chunk semantics.

The contract under test (core.engine / core.bcd._select_block): for the same
seed and config, every backend — sequential reference, vmapped batched,
mesh-sharded — selects bit-identical blocks, because (a) candidate sampling
burns exactly RT rng draws per outer step regardless of backend/chunking,
(b) candidates are scanned in sampling order with first-occurrence argmin
tie-breaking, and (c) the ADT early exit accepts the first candidate below
tolerance and never looks past its chunk.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import bcd, engine, linearize, masks as M, tracing
from repro.data import ImageDatasetCfg, SyntheticImages
from repro.launch import mesh as mesh_lib, sweep
from repro.models.resnet import CNN, CNNConfig
from repro.training import optimizer as opt_lib, train as train_lib


@pytest.fixture(scope="module")
def setup():
    cfg = CNNConfig("tiny", 4, 16, ((8, 1, 1), (16, 1, 2)), stem_channels=8)
    model = CNN(cfg)
    data = SyntheticImages(ImageDatasetCfg(n_classes=4, image_size=16,
                                           n_train=256, n_test=64))
    params = model.init(jax.random.PRNGKey(0))
    step, _ = train_lib.make_cnn_train_step(
        model, opt_lib.sgd(lr=5e-2, momentum=0.9))
    opt = opt_lib.sgd(lr=5e-2, momentum=0.9)
    ostate = opt.init(params)
    batches = data.batches("train", 32)
    masks0 = linearize.init_masks(model.mask_sites())
    mdev = M.as_device(masks0)
    for i in range(40):
        params, ostate, _, _ = step(params, ostate, mdev,
                                    {k: jnp.asarray(v)
                                     for k, v in batches(i).items()})
    batch = data.train_eval_set(128)
    return model, params, batch, masks0


def _run(model, params, batch, masks0, evaluator, chunk_size=4):
    total = M.count(masks0)
    cfg = bcd.BCDConfig(b_target=total - 3 * 16, drc=16, rt=8, adt=0.5,
                        finetune_every_step=False, seed=3,
                        chunk_size=chunk_size)
    eval_acc = model.make_eval_acc(params, batch)
    return bcd.run_bcd(masks0, cfg, eval_acc, evaluator=evaluator)


def _assert_same_result(a, b):
    for k in a.masks:
        np.testing.assert_array_equal(a.masks[k], b.masks[k])
    assert len(a.history) == len(b.history)
    for ha, hb in zip(a.history, b.history):
        assert (ha.trials, ha.found_early) == (hb.trials, hb.found_early)
        assert ha.best_drop == pytest.approx(hb.best_drop, abs=1e-4)
        assert (ha.budget_before, ha.budget_after) == \
            (hb.budget_before, hb.budget_after)


def test_batched_matches_sequential_bitwise(setup):
    model, params, batch, masks0 = setup
    seq = _run(model, params, batch, masks0,
               engine.SequentialEvaluator(model.make_eval_acc(params, batch)))
    bat = _run(model, params, batch, masks0,
               engine.BatchedEvaluator(model.make_eval_fn(params, batch),
                                       pad_to=4))
    _assert_same_result(seq, bat)


def test_sharded_matches_sequential_bitwise(setup):
    model, params, batch, masks0 = setup
    seq = _run(model, params, batch, masks0,
               engine.SequentialEvaluator(model.make_eval_acc(params, batch)))
    shd = _run(model, params, batch, masks0,
               engine.ShardedEvaluator(model.make_eval_fn(params, batch),
                                       mesh_lib.make_candidate_mesh(),
                                       pad_to=4))
    _assert_same_result(seq, shd)


@pytest.mark.parametrize("prefetch", [0, 1, 2])
def test_pipelined_matches_sequential_bitwise(setup, prefetch):
    """Double-buffered staging is a pure latency optimization: for every
    prefetch depth — including 0, the strict materialize→evaluate
    degradation — the pipelined backend selects bit-identical blocks.
    chunk_size=3 against rt=8 forces ragged final chunks (3, 3, 2)."""
    model, params, batch, masks0 = setup
    seq = _run(model, params, batch, masks0,
               engine.SequentialEvaluator(model.make_eval_acc(params, batch)),
               chunk_size=3)
    pip = _run(model, params, batch, masks0,
               engine.PipelinedEvaluator(model.make_eval_fn(params, batch),
                                         pad_to=3, prefetch=prefetch),
               chunk_size=3)
    _assert_same_result(seq, pip)


def test_pipelined_on_mesh_matches_sequential_bitwise(setup):
    """Prefetch pipeline layered over sharded placement (1-D local mesh)."""
    model, params, batch, masks0 = setup
    seq = _run(model, params, batch, masks0,
               engine.SequentialEvaluator(model.make_eval_acc(params, batch)))
    pip = _run(model, params, batch, masks0,
               engine.PipelinedEvaluator(model.make_eval_fn(params, batch),
                                         pad_to=4, prefetch=2,
                                         mesh=mesh_lib.make_candidate_mesh()))
    _assert_same_result(seq, pip)


def test_chunk_size_does_not_change_selection(setup):
    """rng burns RT draws per step regardless of chunking, so chunk_size is
    a pure performance knob: selections are identical."""
    model, params, batch, masks0 = setup
    ev = engine.BatchedEvaluator(model.make_eval_fn(params, batch))
    a = _run(model, params, batch, masks0, ev, chunk_size=1)
    b = _run(model, params, batch, masks0, ev, chunk_size=8)
    for k in a.masks:
        np.testing.assert_array_equal(a.masks[k], b.masks[k])


def test_evaluator_accs_agree(setup):
    """Raw per-candidate accuracies: vmapped batch == sequential loop."""
    model, params, batch, masks0 = setup
    stacked = M.sample_removal_blocks(
        np.random.default_rng(0), masks0, 16, 6)
    seq = engine.SequentialEvaluator(model.make_eval_acc(params, batch))
    bat = engine.BatchedEvaluator(model.make_eval_fn(params, batch))
    np.testing.assert_allclose(bat.evaluate(stacked), seq.evaluate(stacked),
                               atol=1e-4)


def _pad_with_nan(stacked, n):
    """``masks.pad_stacked`` with NaN masks in the padded slots: a padded
    slot a program computed would read an accuracy other than 0."""
    return {k: np.concatenate([v, np.full((n - len(v),) + v.shape[1:],
                                          np.nan, np.float32)])
            for k, v in stacked.items()}


@pytest.mark.parametrize("backend", ["batched", "pipelined"])
@pytest.mark.parametrize("fill", [1, 3, 4])
def test_looped_chunk_matches_sequential_and_skips_padding(
        setup, monkeypatch, backend, fill):
    """On one device a chunk is one program that loops over its real
    candidates: the same accuracies as the sequential reference at every
    fill of a 4-slot chunk, whatever the padded slots hold, and the padded
    slots are never computed (they read 0 and are not counted)."""
    model, params, batch, masks0 = setup
    pad_to = 4
    stacked = M.sample_removal_blocks(np.random.default_rng(fill), masks0,
                                      16, fill)
    want = engine.SequentialEvaluator(
        model.make_eval_acc(params, batch)).evaluate(stacked)
    ev = engine.make_evaluator(backend, pad_to=pad_to, context=params,
                               eval_fn=model.make_param_eval_fn(batch))
    monkeypatch.setattr(M, "pad_stacked", _pad_with_nan)
    with tracing.recording() as rec:
        staged = ev.stage(stacked)
        got = ev.evaluate_staged(staged)
    np.testing.assert_allclose(got, want, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(staged.accs)[fill:], 0.0)
    assert rec.counts["engine.fallback_forwards"] == fill
    assert rec.counts["engine.fallback_slots"] == pad_to
    assert (ev._looped._cache_size(), ev._vmapped._cache_size()) == (1, 0)


def test_bcd_steps_with_looped_fallback_select_like_sequential(setup):
    """Every mask live, so sampled blocks touch shallow sites and the suffix
    engine sends chunks down its looped full-forward fallback: bcd_steps
    still selects the sequential reference's blocks."""
    model, params, batch, masks0 = setup
    cfg = bcd.BCDConfig(b_target=0, drc=16, rt=8, adt=-1000.0, seed=5,
                        chunk_size=4)
    states = {}
    for name in ("sequential", "suffix"):
        ev, eval_acc, _ = sweep.make_bcd_evaluator(
            name, model, batch, {"params": params}, chunk_size=4, rt=8)
        states[name] = bcd.init_state(masks0, cfg)
        gen = bcd.bcd_steps(states[name], cfg, eval_acc, evaluator=ev)
        with tracing.recording() as rec:
            for _ in range(3):
                next(gen)
        gen.close()
        if name == "suffix":
            assert rec.counts["engine.fallback_forwards"] > 0
    seq, sfx = states["sequential"], states["suffix"]
    for k in seq.masks:
        np.testing.assert_array_equal(seq.masks[k], sfx.masks[k])
    for a, b in zip(seq.history, sfx.history):
        assert (a.trials, a.budget_after) == (b.trials, b.budget_after)
        assert a.best_drop == pytest.approx(b.best_drop, abs=1e-4)


def test_lm_eval_closures_batched_matches_sequential():
    """The LM path: masks ride the scanned stack as stacked xs; vmapping the
    candidate axis over the scan must agree with the sequential loop."""
    from repro.configs import get_config
    from repro.models.lm import LM
    cfg = get_config("stablelm_1p6b").reduced()
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {"tokens": np.asarray(
        rng.integers(0, cfg.vocab, (2, 33), dtype=np.int32))}
    masks0 = linearize.init_masks(model.mask_sites())
    stacked = M.sample_removal_blocks(
        np.random.default_rng(1), masks0, 16, 5)
    seq = engine.SequentialEvaluator(model.make_eval_acc(params, batch))
    bat = engine.BatchedEvaluator(model.make_eval_fn(params, batch),
                                  pad_to=3)
    np.testing.assert_allclose(bat.evaluate(stacked), seq.evaluate(stacked),
                               atol=1e-4)


def test_context_swap_is_visible_without_retrace():
    """Params ride as evaluator *context* (a jit input): set_context must
    change results — a closure-captured param tree would silently go stale
    after finetuning."""
    eval_fn = lambda masks, scale: scale * jnp.sum(masks["s"])
    ev = engine.BatchedEvaluator(eval_fn, context=jnp.asarray(1.0))
    stacked = M.sample_removal_blocks(
        np.random.default_rng(0), {"s": np.ones((8,), np.float32)}, 2, 3)
    before = ev.evaluate(stacked)
    np.testing.assert_allclose(before, [6.0, 6.0, 6.0])
    ev.set_context(jnp.asarray(2.0))
    np.testing.assert_allclose(ev.evaluate(stacked), 2 * before)
    with pytest.raises(ValueError):
        engine.BatchedEvaluator(lambda m: jnp.sum(m["s"])).set_context(1.0)
    # the meshless pipelined backend must support the same swap (finetune
    # between outer steps while chunks are staged)
    pip = engine.PipelinedEvaluator(eval_fn, context=jnp.asarray(1.0),
                                    prefetch=2)
    np.testing.assert_allclose(pip.evaluate(stacked), before)
    pip.set_context(jnp.asarray(3.0))
    np.testing.assert_allclose(pip.evaluate(stacked), 3 * before)


_SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax
from repro.core import engine, linearize, masks as M
from repro.data import ImageDatasetCfg, SyntheticImages
from repro.launch import mesh as mesh_lib
from repro.models.resnet import CNN, CNNConfig

model = CNN(CNNConfig("tiny", 4, 8, ((4, 1, 1),), stem_channels=4))
data = SyntheticImages(ImageDatasetCfg(n_classes=4, image_size=8,
                                       n_train=64, n_test=32))
params = model.init(jax.random.PRNGKey(0))
batch = data.train_eval_set(16)
masks0 = linearize.init_masks(model.mask_sites())
stacked = M.sample_removal_blocks(np.random.default_rng(0), masks0, 8, 6)
mesh = mesh_lib.make_candidate_mesh()
assert len(mesh.devices.reshape(-1)) == 4, mesh
seq = engine.SequentialEvaluator(model.make_eval_acc(params, batch))
shd = engine.ShardedEvaluator(model.make_eval_fn(params, batch), mesh)
np.testing.assert_allclose(shd.evaluate(stacked), seq.evaluate(stacked),
                           atol=1e-4)
print("SHARDED_OK")
"""


def test_sharded_on_forced_multi_device_mesh():
    """Real candidate-axis sharding: 4 forced host devices, padding 6
    candidates up to 8 — results identical to the sequential reference."""
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "SHARDED_OK" in out.stdout


_MESH_PATH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax
from repro.core import engine, linearize, masks as M, tracing
from repro.data import ImageDatasetCfg, SyntheticImages
from repro.launch import mesh as mesh_lib
from repro.models.resnet import CNN, CNNConfig

model = CNN(CNNConfig("tiny", 4, 8, ((4, 1, 1),), stem_channels=4))
data = SyntheticImages(ImageDatasetCfg(n_classes=4, image_size=8,
                                       n_train=64, n_test=32))
params = model.init(jax.random.PRNGKey(0))
batch = data.train_eval_set(16)
masks0 = linearize.init_masks(model.mask_sites())
stacked = M.sample_removal_blocks(np.random.default_rng(0), masks0, 8, 6)
want = engine.SequentialEvaluator(
    model.make_eval_acc(params, batch)).evaluate(stacked)
mesh = mesh_lib.make_cand_batch_mesh(cand=2, batch=2)
ctx = {"params": params, "batch": {k: np.asarray(v) for k, v in batch.items()}}
specs = engine.context_batch_specs(ctx)
sfx = engine.make_evaluator("suffix", split=model.make_suffix_eval_fns(),
                            mesh=mesh, context=ctx, context_specs=specs,
                            pad_to=4)
# (evaluator, the chunk evaluator inside it, chunk, slots dispatched): the
# 1-D mesh pads 6 candidates to its 4 devices; the joint mesh lays 6 over
# "cand" alone (docs/bcd_engine.md, "Joint candidate×batch sharding")
cases = (
    (engine.ShardedEvaluator(model.make_eval_fn(params, batch),
                             mesh_lib.make_candidate_mesh()), None,
     stacked, 8),
    (engine.PipelinedEvaluator(model.make_joint_eval_fn(), mesh=mesh,
                               context=ctx, context_specs=specs, pad_to=4),
     None, stacked, 6),
    (sfx, sfx._inner, engine.SitedChunk(None, stacked), 6))
for ev, inner, chunk, slots in cases:
    inner = inner or ev
    with tracing.recording() as rec:
        np.testing.assert_allclose(ev.evaluate(chunk), want, atol=1e-4)
    # the vmap computes every slot it is given, padding included
    assert dict(rec.counts) == {"engine.fallback_forwards": slots,
                                "engine.fallback_slots": slots}, rec.counts
    assert inner._vmapped._cache_size() == 1, ev.name
    assert inner._looped._cache_size() == 0, ev.name
print("MESH_PATH_OK")
"""


def test_meshed_evaluators_keep_the_vmapped_path():
    """Under a mesh the candidate axis is what shards across devices, so
    the sharded, meshed pipelined and meshed suffix fallback evaluators keep
    the vmapped chunk program (every slot computed), not the one-device
    loop — and still match the sequential reference."""
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", _MESH_PATH_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "MESH_PATH_OK" in out.stdout


# ------------------------------------------------- chunked ADT semantics


class _ScriptedEvaluator:
    """Returns scripted accuracies in candidate order; records chunk sizes."""

    name = "scripted"

    def __init__(self, accs):
        self._accs = list(accs)
        self._next = 0
        self.chunks = []

    def evaluate(self, stacked):
        n = M.stacked_len(stacked)
        self.chunks.append(n)
        out = self._accs[self._next:self._next + n]
        self._next += n
        return np.asarray(out, dtype=np.float64)


def _tiny_masks(n=24):
    return {"s": np.ones((n,), np.float32)}


def test_early_exit_stops_at_first_chunk_with_hit():
    """Candidate drops: [1.0, 0.9 | 0.8, 0.1 | ...] with adt=0.3 — the
    second chunk contains the first sub-ADT drop; the third chunk must never
    be evaluated, and the winner is candidate index 3 (trials=4)."""
    masks = _tiny_masks()
    cfg = bcd.BCDConfig(b_target=M.count(masks) - 4, drc=4, rt=6, adt=0.3,
                        chunk_size=2, seed=0)
    acc_base = 90.0
    ev = _ScriptedEvaluator(acc_base - np.array([1.0, 0.9, 0.8, 0.1,
                                                 0.0, 0.0]))
    rng = np.random.default_rng(cfg.seed)
    cand, idx, drop, trials, found, _moves = bcd._select_block(
        masks, cfg, rng, ev, 4, acc_base)
    assert ev.chunks == [2, 2]                  # third chunk never evaluated
    assert (idx, trials, found) == (3, 4, True)
    assert drop == pytest.approx(0.1)
    # the returned tree is candidate 3 of the same sampling stream
    want = M.index_stacked(M.sample_removal_blocks(
        np.random.default_rng(cfg.seed), masks, 4, cfg.rt), 3)
    for k in want:
        np.testing.assert_array_equal(cand[k], want[k])


def test_no_early_exit_takes_first_occurrence_argmin():
    masks = _tiny_masks()
    cfg = bcd.BCDConfig(b_target=M.count(masks) - 4, drc=4, rt=6, adt=-1.0,
                        chunk_size=4, seed=0)
    drops = np.array([1.0, 0.7, 0.9, 0.7, 0.8, 0.7])   # tie at 0.7
    ev = _ScriptedEvaluator(90.0 - drops)
    _, idx, drop, trials, found, _moves = bcd._select_block(
        masks, cfg, np.random.default_rng(0), ev, 4, 90.0)
    assert ev.chunks == [4, 2]                  # all chunks evaluated
    assert (idx, trials, found) == (1, 6, False)
    assert drop == pytest.approx(0.7)


# ------------------------------------------------- prefetch-loop semantics


class _StagedScriptedEvaluator(_ScriptedEvaluator):
    """Scripted accuracies with the staging protocol; logs the event order
    so tests can pin down exactly when chunks are staged vs consumed."""

    name = "scripted-staged"

    def __init__(self, accs, prefetch):
        super().__init__(accs)
        self.prefetch_depth = prefetch
        self.events = []

    def stage(self, stacked):
        n = M.stacked_len(stacked)
        accs = super().evaluate(stacked)
        self.events.append(("stage", self._next - n))
        return engine.StagedChunk(n, accs)

    def evaluate_staged(self, staged):
        # accs were scripted at stage() time; this is the blocking read
        self.events.append(("consume",))
        return staged.accs

    def evaluate(self, stacked):
        self.events.append(("evaluate",))
        return super().evaluate(stacked)


def test_prefetch_loop_stages_ahead_and_consumes_in_order():
    """depth=1: chunk k+1 is staged before chunk k's results are consumed,
    and chunk k+2 is only committed after chunk k was checked."""
    ev = _StagedScriptedEvaluator(90.0 - np.arange(8, dtype=np.float64),
                                  prefetch=1)
    chunks = [M.sample_removal_blocks(np.random.default_rng(i),
                                      _tiny_masks(), 2, 2)
              for i in range(4)]
    out = []
    for accs in engine.evaluate_prefetched(ev, iter(chunks)):
        out.append(accs)
    kinds = [e[0] for e in ev.events]
    assert kinds == ["stage", "stage", "consume", "stage", "consume",
                     "stage", "consume", "consume"]
    np.testing.assert_array_equal(np.concatenate(out),
                                  90.0 - np.arange(8))


def test_prefetch_loop_early_exit_wastes_at_most_depth_chunks():
    """Closing the result generator (the ADT exit) drops staged chunks and
    never materializes chunks beyond the staging horizon."""
    ev = _StagedScriptedEvaluator(np.zeros(12), prefetch=2)
    pulled = []

    def produce():
        for i in range(6):
            pulled.append(i)
            yield M.sample_removal_blocks(np.random.default_rng(i),
                                          _tiny_masks(), 2, 2)

    results = engine.evaluate_prefetched(ev, produce())
    next(results)                 # consume chunk 0; chunks 0..2 are staged
    results.close()
    assert pulled == [0, 1, 2]    # chunks 3..5 never even materialized
    assert [e[0] for e in ev.events] == ["stage", "stage", "stage",
                                         "consume"]


def test_prefetch_depth_zero_degrades_to_strict_alternation():
    ev = _StagedScriptedEvaluator(np.zeros(4), prefetch=0)
    chunks = [M.sample_removal_blocks(np.random.default_rng(i),
                                      _tiny_masks(), 2, 2) for i in range(2)]
    list(engine.evaluate_prefetched(ev, iter(chunks)))
    assert [e[0] for e in ev.events] == ["evaluate", "evaluate"]


# -------------------------------------------- joint candidate×batch sharding


_JOINT_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax
from repro.core import engine, linearize, masks as M
from repro.data import ImageDatasetCfg, SyntheticImages
from jax.sharding import PartitionSpec as P
from repro.launch import mesh as mesh_lib
from repro.models.resnet import CNN, CNNConfig

model = CNN(CNNConfig("tiny", 4, 8, ((4, 1, 1),), stem_channels=4))
data = SyntheticImages(ImageDatasetCfg(n_classes=4, image_size=8,
                                       n_train=64, n_test=32))
params = model.init(jax.random.PRNGKey(0))
batch = data.train_eval_set(16)
masks0 = linearize.init_masks(model.mask_sites())
stacked = M.sample_removal_blocks(np.random.default_rng(0), masks0, 8, 6)

mesh = mesh_lib.make_cand_batch_mesh(cand=2, batch=2)
assert tuple(mesh.axis_names) == ("cand", "batch"), mesh
assert mesh.devices.size == 4, mesh
ctx = {"params": params, "batch": {k: np.asarray(v) for k, v in batch.items()}}
ev = engine.ShardedEvaluator(model.make_joint_eval_fn(), mesh, context=ctx,
                             context_specs=engine.context_batch_specs(ctx))
seq = engine.SequentialEvaluator(model.make_eval_acc(params, batch))

# per-call PartitionSpec selection: a 2-candidate chunk (< 4 devices) must
# take the cand-only layout (batch axis splits the forward); a full chunk
# takes the joint layout over both axes
n2, s2 = ev._chunk_sharding(2)
assert (n2, s2.spec) == (2, P("cand")), (n2, s2.spec)
n8, s8 = ev._chunk_sharding(8)
assert (n8, s8.spec) == (8, P(("cand", "batch"))), (n8, s8.spec)

small = M.slice_stacked(stacked, 0, 2)
np.testing.assert_allclose(ev.evaluate(small), seq.evaluate(small), atol=1e-4)
np.testing.assert_allclose(ev.evaluate(stacked), seq.evaluate(stacked),
                           atol=1e-4)

# pipelined over the same joint mesh, with a context swap (re-sharded)
pip = engine.PipelinedEvaluator(model.make_joint_eval_fn(), mesh=mesh,
                                prefetch=2, context=ctx,
                                context_specs=engine.context_batch_specs(ctx))
np.testing.assert_allclose(pip.evaluate(small), seq.evaluate(small),
                           atol=1e-4)
pip.set_context(ctx)
np.testing.assert_allclose(pip.evaluate(stacked), seq.evaluate(stacked),
                           atol=1e-4)
print("JOINT_OK")
"""


def test_joint_cand_batch_sharding_on_forced_multi_device_mesh():
    """4 forced host devices on a ("cand", "batch") = (2, 2) mesh: small
    chunks shard candidates over "cand" while the batch-sharded context
    splits each forward over "batch"; results match the sequential
    reference bit-for-bit at evaluation tolerance."""
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", _JOINT_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "JOINT_OK" in out.stdout


_KERNEL_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax
from jax.experimental import pallas as pl
from repro.core import engine, linearize, masks as M
from repro.data import ImageDatasetCfg, SyntheticImages
from repro.kernels import ops
from repro.launch import mesh as mesh_lib
from repro.models.resnet import CNN, CNNConfig

model = CNN(CNNConfig("tiny", 4, 8, ((4, 1, 1), (8, 1, 2)), stem_channels=4))
data = SyntheticImages(ImageDatasetCfg(n_classes=4, image_size=8,
                                       n_train=64, n_test=32))
params = model.init(jax.random.PRNGKey(0))
batch = data.train_eval_set(16)
masks0 = linearize.init_masks(model.mask_sites())
stacked = M.sample_removal_blocks(np.random.default_rng(0), masks0, 8, 6)
deep = model.site_order()[-1]
sited = M.materialize_candidates(masks0, M.sample_removal_indices_within(
    np.random.default_rng(1), masks0, 4, 6, [deep]))
seq = engine.SequentialEvaluator(model.make_eval_acc(params, batch))
want, want_deep = seq.evaluate(stacked), seq.evaluate(sited)

# the TPU dispatch on host devices: every hard-mask site and fused suffix
# site runs its Pallas kernel (interpret mode), per device shard
_pallas_call = pl.pallas_call
pl.pallas_call = lambda *a, **k: _pallas_call(*a, **{**k, "interpret": True})
ops._use_pallas = lambda: True
shard_meshes = []
_per_shard = ops._per_shard
def counting(kernel, args, roles):
    shard_meshes.append(jax.sharding.get_abstract_mesh().size)
    return _per_shard(kernel, args, roles)
ops._per_shard = counting

mesh = mesh_lib.make_cand_batch_mesh(cand=2, batch=2)
ctx = {"params": params, "batch": {k: np.asarray(v) for k, v in batch.items()}}
specs = engine.context_batch_specs(ctx)
ev = engine.ShardedEvaluator(model.make_joint_eval_fn(), mesh, context=ctx,
                             context_specs=specs)
# a full chunk (joint layout) and a 2-candidate chunk (cand-only layout)
np.testing.assert_allclose(ev.evaluate(stacked), want, atol=1e-4)
np.testing.assert_allclose(ev.evaluate(M.slice_stacked(stacked, 0, 2)),
                           want[:2], atol=1e-4)
sfx = engine.make_evaluator("suffix", split=model.make_suffix_eval_fns(),
                            mesh=mesh, context=ctx, context_specs=specs,
                            pad_to=4, fused_kernels=True)
sfx.begin_step(masks0)
np.testing.assert_allclose(sfx.evaluate(engine.SitedChunk(deep, sited)),
                           want_deep, atol=1e-4)
assert shard_meshes and set(shard_meshes) == {4}, shard_meshes
print("KERNEL_MESH_OK")
"""


def test_kernels_run_per_shard_on_forced_multi_device_mesh():
    """The TPU kernel dispatch under a 4-device ("cand", "batch") mesh:
    Mosaic kernels cannot be partitioned automatically, so every kernel
    runs per device shard (``ops._per_shard``), in the sharded engine's
    joint and cand-only layouts and in the suffix engine's fused suffix —
    and still matches the sequential reference."""
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", _KERNEL_MESH_SCRIPT],
                         env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "KERNEL_MESH_OK" in out.stdout


# ------------------------------------------------- prefetch auto-tuning


def test_auto_prefetch_picks_depth_and_matches_sequential(setup):
    """prefetch='auto': the first chunks probe producer vs consumer rates
    in strict alternation, then the depth locks in for the rest of the run
    — and selection stays bit-identical to the sequential reference."""
    model, params, batch, masks0 = setup
    seq = _run(model, params, batch, masks0,
               engine.SequentialEvaluator(model.make_eval_acc(params, batch)),
               chunk_size=2)
    ev = engine.PipelinedEvaluator(model.make_eval_fn(params, batch),
                                   pad_to=2, prefetch="auto")
    assert ev.prefetch_depth == 0 and not ev.auto_tuner.done
    pip = _run(model, params, batch, masks0, ev, chunk_size=2)
    _assert_same_result(seq, pip)
    assert ev.auto_tuner.done
    assert 1 <= ev.prefetch_depth <= ev.auto_tuner.max_depth
    assert set(ev.auto_report) == {"producer_s", "consumer_s", "prefetch",
                                   "samples"}
    assert ev.auto_report["prefetch"] == ev.prefetch_depth


def test_auto_tuner_depth_formula():
    t = engine.PrefetchAutoTuner(n_probe=2, max_depth=4)
    t.add_sample(1.0, 1.0)              # warm-up (compile) — discarded
    t.add_sample(0.001, 0.0095)
    assert not t.done
    t.add_sample(0.001, 0.0105)
    assert t.done
    assert t.depth() == 4               # floor(10) capped at max_depth
    slow_prod = engine.PrefetchAutoTuner(n_probe=1, max_depth=4)
    slow_prod.add_sample(1.0, 1.0)
    slow_prod.add_sample(0.05, 0.001)   # producer-bound: still overlap once
    assert slow_prod.done and slow_prod.depth() == 1


def test_make_evaluator_accepts_auto_prefetch():
    ev = engine.make_evaluator("pipelined",
                               eval_fn=lambda m: jnp.sum(m["s"]),
                               prefetch="auto")
    assert ev.auto_tuner is not None and ev.prefetch_depth == 0
    with pytest.raises(ValueError):
        engine.make_evaluator("pipelined", eval_fn=lambda m: 0.0,
                              prefetch="bogus")
    # backends without a staging pipeline must reject 'auto' loudly rather
    # than silently running untuned
    for backend in ("sequential", "batched", "sharded"):
        with pytest.raises(ValueError, match="pipelined"):
            engine.make_evaluator(backend, eval_acc=lambda m: 0.0,
                                  eval_fn=lambda m: 0.0, prefetch="auto")


# ------------------------------------------------------------- hardening


def test_invalid_configs_raise_upfront():
    masks = _tiny_masks()
    eval_acc = lambda m: 90.0
    for bad in (dict(rt=0), dict(drc=0), dict(chunk_size=0),
                dict(b_target=-1), dict(adt=float("nan"))):
        kw = {"b_target": 8, "drc": 4, "rt": 4, **bad}
        cfg = bcd.BCDConfig(**kw)
        with pytest.raises(ValueError):
            bcd.run_bcd(masks, cfg, eval_acc)


def test_target_at_or_above_start_is_noop():
    masks = _tiny_masks()
    cfg = bcd.BCDConfig(b_target=M.count(masks), drc=4, rt=4)
    res = bcd.run_bcd(masks, cfg, lambda m: 90.0)
    assert res.history == [] and M.count(res.masks) == M.count(masks)


def test_make_evaluator_factory_validates():
    with pytest.raises(ValueError):
        engine.make_evaluator("sequential")
    with pytest.raises(ValueError):
        engine.make_evaluator("batched")
    with pytest.raises(ValueError):
        engine.make_evaluator("pipelined")
    with pytest.raises(ValueError):
        engine.make_evaluator("nope", eval_acc=lambda m: 0.0)
    with pytest.raises(ValueError):        # negative prefetch
        engine.make_evaluator("pipelined", eval_fn=lambda m: 0.0,
                              prefetch=-1)
    with pytest.raises(ValueError):        # context_specs needs a mesh
        engine.PipelinedEvaluator(lambda m: 0.0, context={"batch": {}},
                                  context_specs={"batch": {}})
    ev = engine.make_evaluator("pipelined",
                               eval_fn=lambda m: jnp.sum(m["s"]),
                               prefetch=2)
    assert ev.prefetch_depth == 2 and ev.name == "pipelined"
    ev = engine.make_evaluator("sequential", eval_acc=lambda m: 42.0)
    accs = ev.evaluate(M.sample_removal_blocks(
        np.random.default_rng(0), _tiny_masks(), 2, 3))
    np.testing.assert_array_equal(accs, [42.0, 42.0, 42.0])
