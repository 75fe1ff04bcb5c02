"""PrefixTrie invariants + the suffix engine's trie lifetime.

The trie is the suffix backend's working set: device-resident prefix
activations keyed by cut-segment depth.  Under test here:

* **Lookup** returns the *deepest* cached ancestor at or above the
  requested depth (chain structure: depth d is an ancestor of every
  deeper entry).
* **Eviction** strictly respects the byte budget after every insert, is
  LRU-first with a shallow-first tie-break, and drops the just-inserted
  entry last.
* **Extension** — ``prefix_ext(a→b, prefix(a)) == prefix(b)`` bitwise at
  the model layer (both families), the contract that lets the engine
  fold only the segments between a cached ancestor and the cut.
* **Lifetime** — unchanged base masks keep entries across ``begin_step``;
  an edit at segment s drops exactly the depths > s; a byte budget small
  enough to thrash never changes selection (the trie is a pure cache).
"""
import numpy as np
import jax
import pytest

# hypothesis is an optional dev dep (pip extra: test) — bare environments
# must still collect/run the deterministic property sweep below, so only
# the @given tests are guarded.
try:
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ImportError:
    HAS_HYPOTHESIS = False

from repro.configs.base import ArchConfig, Block
from repro.core import bcd, engine, linearize, masks as M
from repro.core.engine import PrefixTrie, tree_nbytes
from repro.data import ImageDatasetCfg, SyntheticImages
from repro.models.lm import LM
from repro.models.resnet import CNN, CNNConfig


# ------------------------------------------------------------ unit level


def test_tree_nbytes_sums_leaves():
    t = {"a": np.zeros((4, 4), np.float32),
         "b": [np.zeros((2,), np.float16), np.zeros((3,), np.int32)]}
    assert tree_nbytes(t) == 4 * 4 * 4 + 2 * 2 + 3 * 4


def test_trie_lookup_returns_deepest_ancestor():
    t = PrefixTrie()
    t.insert(1, "p1", nbytes=1)
    t.insert(3, "p3", nbytes=1)
    assert t.lookup(0) is None
    assert t.lookup(1) == (1, "p1")
    assert t.lookup(2) == (1, "p1")
    assert t.lookup(3) == (3, "p3")
    assert t.lookup(9) == (3, "p3")
    assert t.depths() == (1, 3)
    assert 3 in t and 2 not in t and len(t) == 2


def test_trie_rejects_negative_budget():
    with pytest.raises(ValueError, match="budget_bytes"):
        PrefixTrie(budget_bytes=-1)


def test_trie_eviction_respects_budget_lru_then_shallow():
    t = PrefixTrie(budget_bytes=10)
    t.insert(1, "p1", nbytes=4)
    t.insert(2, "p2", nbytes=4)
    t.lookup(1)                      # touch depth 1 -> depth 2 becomes LRU
    t.insert(3, "p3", nbytes=4)      # over budget: evict LRU depth 2
    assert t.depths() == (1, 3) and t.total_bytes() == 8
    assert t.evictions == 1
    # just-inserted entry survives even when everything else must go
    t.insert(5, "p5", nbytes=9)
    assert t.depths() == (5,)
    # an entry that alone exceeds the budget is dropped too (caller keeps
    # the returned reference for in-flight dispatches)
    t.insert(6, "p6", nbytes=11)
    assert len(t) == 0
    assert t.total_bytes() == 0


def test_trie_eviction_tie_break_is_shallow_first():
    t = PrefixTrie(budget_bytes=8)
    t.insert(2, "p2", nbytes=4)
    t.insert(4, "p4", nbytes=4)
    # equal-tick ties are impossible (monotone clock); emulate "oldest
    # equally cold" by never touching either, then force one eviction:
    t.insert(6, "p6", nbytes=4)      # evicts depth 2 (oldest tick)
    assert t.depths() == (4, 6)


def test_trie_keep_where_and_clear():
    t = PrefixTrie()
    for d in (1, 2, 4):
        t.insert(d, f"p{d}", nbytes=1)
    t.keep_where(lambda d: d <= 2)
    assert t.depths() == (1, 2)
    t.clear()
    assert len(t) == 0 and t.total_bytes() == 0


def _check_invariants(trie, budget, mirror):
    """The two properties under test, against a dict mirror of inserts."""
    if budget is not None:
        assert trie.total_bytes() <= budget
    for probe in range(0, 12):
        got = trie.lookup(probe)
        live = [d for d in trie.depths() if d <= probe]
        if not live:
            assert got is None
        else:
            d = max(live)
            assert got == (d, mirror[d])


def _drive(ops, budget):
    trie = PrefixTrie(budget_bytes=budget)
    mirror = {}
    for op, depth, nbytes in ops:
        if op == "insert":
            mirror[depth] = f"v{depth}.{nbytes}"
            trie.insert(depth, mirror[depth], nbytes=nbytes)
        else:
            trie.lookup(depth)
        _check_invariants(trie, budget, mirror)


def test_trie_property_sweep_deterministic():
    """Seeded randomized op sequences: lookup always returns the deepest
    cached ancestor <= the probe, and total bytes never exceed the budget
    after any insert — runs even without hypothesis installed."""
    rng = np.random.default_rng(0)
    for case in range(50):
        budget = None if case % 5 == 0 else int(rng.integers(0, 40))
        ops = [("insert" if rng.random() < 0.6 else "lookup",
                int(rng.integers(0, 10)), int(rng.integers(1, 12)))
               for _ in range(rng.integers(1, 25))]
        _drive(ops, budget)


if HAS_HYPOTHESIS:
    @given(
        budget=st.one_of(st.none(), st.integers(0, 40)),
        ops=st.lists(st.tuples(st.sampled_from(["insert", "lookup"]),
                               st.integers(0, 10), st.integers(1, 12)),
                     min_size=1, max_size=30),
    )
    @settings(max_examples=60, deadline=None)
    def test_trie_property_lookup_and_budget(budget, ops):
        _drive(ops, budget)
else:
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_trie_property_lookup_and_budget():
        pass


# ---------------------------------------------- prefix-extension contract


def test_cnn_prefix_extension_bitwise():
    model = CNN(CNNConfig("tiny", 4, 16, ((8, 1, 1), (16, 1, 2)),
                          stem_channels=8))
    params = model.init(jax.random.PRNGKey(0))
    masks = linearize.init_masks(model.mask_sites())
    masks = M.sample_removal_block(np.random.default_rng(0), masks, 32)
    md = M.as_device(masks)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16, 3))
    order, segs = model.site_order(), model.site_segments()
    for a in order:
        pa = jax.jit(lambda p, m, x: model.forward_prefix(p, m, x, a))(
            params, md, x)
        for b in order:
            if segs[b] <= segs[a]:
                continue
            want = jax.jit(
                lambda p, m, x: model.forward_prefix(p, m, x, b))(
                    params, md, x)
            got = jax.jit(
                lambda p, m, c: model.forward_prefix(
                    p, m, None, b, from_site=a, cached=c))(params, md, pa)
            np.testing.assert_array_equal(
                np.asarray(got), np.asarray(want),
                err_msg=f"prefix_ext({a} -> {b}) != prefix({b})")


def test_lm_prefix_extension_bitwise():
    cfg = ArchConfig(
        name="tiny-ext", family="dense", n_layers=6, d_model=32,
        n_heads=2, n_kv_heads=2, d_ff=48, vocab=64, head_dim=16,
        pattern=(Block("dense"), Block("dense")),
        head_blocks=(Block("dense"),), dtype="float32")
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    masks = linearize.init_masks(model.mask_sites())
    rng = np.random.default_rng(0)
    masks = M.sample_removal_block(rng, masks, 16)
    md = M.as_device(masks)
    tokens = np.asarray(rng.integers(0, cfg.vocab, (2, 9), dtype=np.int32))
    order, segs = model.site_order(), model.site_segments()
    for a in order:
        pa = jax.jit(lambda p, m, t: model.forward_prefix(p, m, t, a))(
            params, md, tokens)
        for b in order:
            if segs[b] <= segs[a]:
                continue
            want = jax.jit(
                lambda p, m, t: model.forward_prefix(p, m, t, b))(
                    params, md, tokens)
            got = jax.jit(
                lambda p, m, c: model.forward_prefix(
                    p, m, None, b, from_site=a, cached=c))(params, md, pa)
            np.testing.assert_array_equal(
                np.asarray(got), np.asarray(want),
                err_msg=f"prefix_ext({a} -> {b}) != prefix({b})")


# ----------------------------------------------- engine trie lifetime


@pytest.fixture(scope="module")
def setup():
    model = CNN(CNNConfig("tiny", 4, 16, ((8, 1, 1), (16, 1, 2)),
                          stem_channels=8))
    data = SyntheticImages(ImageDatasetCfg(n_classes=4, image_size=16,
                                           n_train=256, n_test=64))
    params = model.init(jax.random.PRNGKey(0))
    batch = data.train_eval_set(128)
    masks0 = linearize.init_masks(model.mask_sites())
    return model, params, batch, masks0


def _suffix_ev(model, params, batch, **kw):
    ctx = {"params": params,
           "batch": {k: np.asarray(v) for k, v in batch.items()}}
    return engine.SuffixEvaluator(model.make_suffix_eval_fns(), context=ctx,
                                  **kw)


def test_engine_extends_ancestor_instead_of_recomputing(setup):
    """Shallow-to-deep chunk order: the second sited chunk extends the
    first chunk's cached prefix (extension counter, not a second miss),
    and the accuracies still match the sequential reference."""
    model, params, batch, masks0 = setup
    order, segs = model.site_order(), model.site_segments()
    deep = order[-1]
    mid = max((s for s in order if segs[s] < segs[deep]),
              key=lambda s: segs[s])
    rng = np.random.default_rng(0)
    idx_mid = M.sample_removal_indices_within(rng, masks0, 16, 4, [mid])
    idx_deep = M.sample_removal_indices_within(rng, masks0, 16, 4, [deep])
    ev = _suffix_ev(model, params, batch, pad_to=4)
    seq = engine.SequentialEvaluator(model.make_eval_acc(params, batch))
    ev.begin_step(masks0)
    for site, idx in ((mid, idx_mid), (deep, idx_deep)):
        stacked = M.materialize_candidates(masks0, idx)
        np.testing.assert_allclose(
            ev.evaluate(engine.SitedChunk(site, stacked)),
            seq.evaluate(stacked), atol=1e-4)
    assert ev.trie.misses == 1 and ev.trie.extensions == 1
    assert ev.trie.depths() == (segs[mid], segs[deep])


def test_engine_covered_fraction_tracks_trie(setup):
    model, params, batch, masks0 = setup
    order, segs = model.site_order(), model.site_segments()
    deep = order[-1]
    fr = model.site_prefix_fractions()
    ev = _suffix_ev(model, params, batch, pad_to=4)
    ev.begin_step(masks0)
    assert ev.covered_fraction(deep) == 0.0
    idx = np.asarray(M.sample_removal_indices_within(
        np.random.default_rng(0), masks0, 16, 4, [deep]))
    ev.evaluate(engine.SitedChunk(
        deep, M.materialize_candidates(masks0, idx)))
    # the deep prefix is now resident: nothing left to compute for a cut
    # at the same depth, and a deeper cut would only pay the increment
    assert ev.covered_fraction(deep) == pytest.approx(fr[deep])
    shallow = order[0]
    assert ev.covered_fraction(shallow) == 0.0


def test_trie_budget_thrash_does_not_change_selection(setup):
    """trie_budget_bytes=0 evicts every entry right after insert — each
    chunk recomputes its prefix, but selection is bit-identical (the trie
    is a pure cache, never semantics)."""
    model, params, batch, masks0 = setup
    total = M.count(masks0)
    cfg = bcd.BCDConfig(b_target=total - 3 * 16, drc=16, rt=8, adt=0.5,
                        finetune_every_step=False, seed=3, chunk_size=4)
    eval_acc = model.make_eval_acc(params, batch)
    ref = bcd.run_bcd(masks0, cfg, eval_acc,
                      evaluator=engine.SequentialEvaluator(eval_acc))
    tight = bcd.run_bcd(masks0, cfg, eval_acc,
                        evaluator=_suffix_ev(model, params, batch,
                                             pad_to=4,
                                             trie_budget_bytes=0))
    for k in ref.masks:
        np.testing.assert_array_equal(ref.masks[k], tight.masks[k])
    assert [h.trials for h in ref.history] == \
        [h.trials for h in tight.history]


def test_engine_multi_step_trie_reuse_matches_sequential(setup):
    """Full run_bcd with a warm trie carried across outer steps, under the
    analytic cost model at thresholds that send every multi-candidate
    chunk down the suffix path, stays bit-identical to the sequential
    reference."""
    model, params, batch, masks0 = setup
    total = M.count(masks0)
    cfg = bcd.BCDConfig(b_target=total - 4 * 12, drc=12, rt=8, adt=0.5,
                        finetune_every_step=False, seed=5, chunk_size=3)
    eval_acc = model.make_eval_acc(params, batch)
    ref = bcd.run_bcd(masks0, cfg, eval_acc,
                      evaluator=engine.SequentialEvaluator(eval_acc))
    cm = engine.SuffixCostModel(min_prefix_fraction=0.0)
    suf = bcd.run_bcd(masks0, cfg, eval_acc,
                      evaluator=_suffix_ev(model, params, batch,
                                           pad_to=3, cost_model=cm))
    for k in ref.masks:
        np.testing.assert_array_equal(ref.masks[k], suf.masks[k])
    for ha, hb in zip(ref.history, suf.history):
        assert (ha.trials, ha.found_early) == (hb.trials, hb.found_early)
