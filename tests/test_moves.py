"""Move-vocabulary conformance: backends × move kinds, bit-identical.

The contract under test (core.masks moves + core.bcd + core.engine): the
typed move vocabulary — remove / add_back / swap / stage_drop / share — is
invisible to the backend-equivalence guarantees.  For the same seed and
config, every backend must select bit-identical moves with identical trial
counts and early-exit flags, for every kind alone and for the mixed-kind
sensitivity-guided sampler, because (a) sampling happens entirely up front
on the host rng, (b) selection is a pure function of the drop vector, and
(c) multi-site candidates group by the *shallowest* touched site, so the
suffix backend's cached prefixes never read an edited mask.

Also here: the move algebra properties (swap ≡ add_back ∘ remove, exact
-drc billing, no out-of-layout resurrection), the PI-cost identity for
share-tied masks, and the two engine regression cases — two-segment moves
never straddling a SitedChunk, and the prefix trie invalidating down to the
shallower of two touched segments.
"""
import numpy as np
import jax
import pytest

from repro.core import bcd, engine, linearize, masks as M, pi_cost
from repro.data import ImageDatasetCfg, SyntheticImages
from repro.launch import mesh as mesh_lib
from repro.models.resnet import CNN, CNNConfig

try:
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ImportError:
    HAS_HYPOTHESIS = False

# the pipelined evaluator read strictly (depth 0), over a 1-D local mesh
# (the vmapped chunk program), and staged ahead at its own depth
BACKENDS = ("sequential", "depth0", "mesh", "pipelined", "suffix")
MIXED = M.MOVE_KINDS                 # all five kinds in one config


# --------------------------------------------------------------- fixture


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


def _make_ev(backend, model, params, batch, prefetch=None):
    if backend == "sequential":
        return engine.SequentialEvaluator(model.make_eval_acc(params, batch))
    if backend == "suffix":
        ctx = {"params": params,
               "batch": {k: np.asarray(v) for k, v in batch.items()}}
        ev = engine.SuffixEvaluator(model.make_suffix_eval_fns(),
                                    context=ctx, pad_to=3)
    elif backend in ("depth0", "mesh", "pipelined"):
        ev = engine.PipelinedEvaluator(
            model.make_eval_fn(params, batch), pad_to=3,
            mesh=mesh_lib.make_candidate_mesh() if backend == "mesh"
            else None)
        if backend != "pipelined":
            ev.prefetch_depth = 0
    else:
        raise AssertionError(backend)
    if prefetch is not None:
        ev.prefetch_depth = prefetch
    return ev


def _run(model, params, batch, masks0, evaluator, moves,
         proposal="uniform"):
    total = M.count(masks0)
    cfg = bcd.BCDConfig(b_target=total - 3 * 16, drc=16, rt=6, adt=0.5,
                        finetune_every_step=False, seed=3, chunk_size=3,
                        moves=moves, proposal=proposal)
    eval_acc = model.make_eval_acc(params, batch)
    return bcd.run_bcd(masks0, cfg, eval_acc, evaluator=evaluator)


def _assert_same_result(a, b):
    for k in a.masks:
        np.testing.assert_array_equal(a.masks[k], b.masks[k])
    assert len(a.history) == len(b.history)
    for ha, hb in zip(a.history, b.history):
        assert (ha.trials, ha.found_early, ha.move_kind) == \
            (hb.trials, hb.found_early, hb.move_kind)
        assert ha.best_drop == pytest.approx(hb.best_drop, abs=1e-4)
        assert (ha.budget_before, ha.budget_after) == \
            (hb.budget_before, hb.budget_after)
    assert a.move_stats == b.move_stats


@pytest.fixture(scope="module")
def seq_ref(setup):
    """Memoized sequential reference per (moves, proposal) — every matrix
    cell compares against the same run."""
    model, params, batch, masks0 = setup
    cache = {}

    def ref(moves, proposal="uniform"):
        key = (tuple(moves), proposal)
        if key not in cache:
            cache[key] = _run(model, params, batch, masks0,
                              _make_ev("sequential", model, params, batch),
                              moves, proposal)
        return cache[key]
    return ref


# ----------------------------------------------- the conformance matrix


@pytest.mark.parametrize("backend", BACKENDS[1:])
@pytest.mark.parametrize("kind", M.MOVE_KINDS)
def test_backend_matches_sequential_per_kind(setup, seq_ref, backend, kind):
    """{depth0, mesh, pipelined, suffix} × {remove, add_back, swap,
    stage_drop, share}: bit-identical masks, trial counts, early-exit flags
    and acceptance stats vs the sequential reference."""
    model, params, batch, masks0 = setup
    res = _run(model, params, batch, masks0,
               _make_ev(backend, model, params, batch), (kind,))
    _assert_same_result(seq_ref((kind,)), res)


@pytest.mark.parametrize("backend", BACKENDS[1:])
def test_backend_matches_sequential_mixed_sensitivity(setup, seq_ref,
                                                      backend):
    """All five kinds under the sensitivity-guided proposal: the kind draw
    and per-site weighting read only (rng, move_stats), so every backend
    replays the identical candidate stream."""
    model, params, batch, masks0 = setup
    res = _run(model, params, batch, masks0,
               _make_ev(backend, model, params, batch), MIXED,
               proposal="sensitivity")
    _assert_same_result(seq_ref(MIXED, "sensitivity"), res)


@pytest.mark.parametrize("prefetch", [0, 1, 2])
def test_suffix_mixed_moves_at_every_prefetch_depth(setup, seq_ref,
                                                    prefetch):
    """The suffix backend's site-major replay with typed multi-site moves,
    at prefetch 0 (strict), 1 (double-buffered) and 2."""
    model, params, batch, masks0 = setup
    res = _run(model, params, batch, masks0,
               _make_ev("suffix", model, params, batch, prefetch=prefetch),
               MIXED)
    _assert_same_result(seq_ref(MIXED), res)


# ------------------------------------ family matrix (SSM / RWKV / MoE)
#
# The same backend×move contract on recurrent and mixture-of-experts
# families: candidates cut the scanned stack mid-repeat (carry-checkpointed
# suffix prefixes) and, for MoE, flow through capacity-overflow token
# dropping — both must stay invisible to selection.

FAMILY_ARCHS = ("rwkv6_3b", "deepseek_moe_16b")
FAMILY_KINDS = ("remove", "swap", "stage_drop")


@pytest.fixture(scope="module")
def family_setup():
    from repro.configs.base import get_config
    from repro.models.lm import LM
    out = {}
    for arch in FAMILY_ARCHS:
        model = LM(get_config(arch).reduced())
        params = model.init(jax.random.PRNGKey(0))
        batch = {"tokens": np.random.default_rng(0).integers(
            0, model.cfg.vocab, (2, 17)).astype(np.int32)}
        masks0 = linearize.init_masks(model.mask_sites())
        out[arch] = (model, params, batch, masks0)
    return out


@pytest.fixture(scope="module")
def family_seq_ref(family_setup):
    cache = {}

    def ref(arch, moves):
        key = (arch, tuple(moves))
        if key not in cache:
            model, params, batch, masks0 = family_setup[arch]
            cache[key] = _run(model, params, batch, masks0,
                              _make_ev("sequential", model, params, batch),
                              moves)
        return cache[key]
    return ref


@pytest.mark.parametrize("backend", BACKENDS[1:])
@pytest.mark.parametrize("kind", FAMILY_KINDS)
@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_family_backend_matches_sequential_per_kind(family_setup,
                                                    family_seq_ref, arch,
                                                    backend, kind):
    """{rwkv6, deepseek-moe} × {depth0, mesh, pipelined, suffix} ×
    {remove, swap, stage_drop}: bit-identical masks, trial counts and
    early-exit flags vs the per-family sequential reference."""
    model, params, batch, masks0 = family_setup[arch]
    res = _run(model, params, batch, masks0,
               _make_ev(backend, model, params, batch), (kind,))
    _assert_same_result(family_seq_ref(arch, (kind,)), res)


@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_family_suffix_mixed_moves(family_setup, family_seq_ref, arch):
    """All three kinds in one descent on the suffix backend — mid-scan
    stack cuts and head/shared sites interleave in the candidate stream."""
    model, params, batch, masks0 = family_setup[arch]
    res = _run(model, params, batch, masks0,
               _make_ev("suffix", model, params, batch), FAMILY_KINDS)
    _assert_same_result(family_seq_ref(arch, FAMILY_KINDS), res)


_FAMILY_SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax
from repro.configs.base import get_config
from repro.core import bcd, engine, linearize, masks as M
from repro.launch import mesh as mesh_lib
from repro.models.lm import LM

for arch in ("rwkv6_3b", "deepseek_moe_16b"):
    model = LM(get_config(arch).reduced())
    params = model.init(jax.random.PRNGKey(0))
    batch = {"tokens": np.random.default_rng(0).integers(
        0, model.cfg.vocab, (2, 17)).astype(np.int32)}
    masks0 = linearize.init_masks(model.mask_sites())
    cfg = bcd.BCDConfig(b_target=M.count(masks0) - 2 * 16, drc=16, rt=6,
                        adt=0.5, finetune_every_step=False, seed=3,
                        chunk_size=3, moves=("remove", "swap", "stage_drop"))
    eval_acc = model.make_eval_acc(params, batch)
    seq = bcd.run_bcd(masks0, cfg, eval_acc,
                      evaluator=engine.SequentialEvaluator(eval_acc))
    mesh = mesh_lib.make_candidate_mesh()
    assert len(mesh.devices.reshape(-1)) == 4, mesh
    shd = bcd.run_bcd(masks0, cfg, eval_acc,
                      evaluator=engine.PipelinedEvaluator(
                          model.make_eval_fn(params, batch), mesh=mesh,
                          pad_to=3))
    for k in seq.masks:
        np.testing.assert_array_equal(seq.masks[k], shd.masks[k])
    assert [(h.trials, h.found_early, h.move_kind) for h in seq.history] \
        == [(h.trials, h.found_early, h.move_kind) for h in shd.history]
    assert seq.move_stats == shd.move_stats
    print(arch, "FAMILY_SHARDED_OK")
"""


def test_family_moves_on_forced_multi_device_mesh():
    """SSM + MoE mixed-kind descent on 4 forced host devices: candidate-
    axis sharding over scanned-stack masks selects the identical moves as
    the sequential reference."""
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", _FAMILY_SHARDED_SCRIPT],
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.count("FAMILY_SHARDED_OK") == 2


_MOVES_SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax
from repro.core import bcd, engine, linearize, masks as M
from repro.data import ImageDatasetCfg, SyntheticImages
from repro.launch import mesh as mesh_lib
from repro.models.resnet import CNN, CNNConfig

model = CNN(CNNConfig("tiny", 4, 8, ((4, 1, 1),), stem_channels=4))
data = SyntheticImages(ImageDatasetCfg(n_classes=4, image_size=8,
                                       n_train=64, n_test=32))
params = model.init(jax.random.PRNGKey(0))
batch = data.train_eval_set(16)
masks0 = linearize.init_masks(model.mask_sites())
cfg = bcd.BCDConfig(b_target=M.count(masks0) - 2 * 8, drc=8, rt=6, adt=0.5,
                    finetune_every_step=False, seed=3, chunk_size=3,
                    moves=M.MOVE_KINDS, proposal="sensitivity")
eval_acc = model.make_eval_acc(params, batch)
seq = bcd.run_bcd(masks0, cfg, eval_acc,
                  evaluator=engine.SequentialEvaluator(eval_acc))
mesh = mesh_lib.make_candidate_mesh()
assert len(mesh.devices.reshape(-1)) == 4, mesh
shd = bcd.run_bcd(masks0, cfg, eval_acc,
                  evaluator=engine.PipelinedEvaluator(
                      model.make_eval_fn(params, batch), mesh=mesh,
                      pad_to=3))
for k in seq.masks:
    np.testing.assert_array_equal(seq.masks[k], shd.masks[k])
assert [h.move_kind for h in seq.history] == \
    [h.move_kind for h in shd.history]
assert seq.move_stats == shd.move_stats
print("MOVES_SHARDED_OK")
"""


def test_mixed_moves_on_forced_multi_device_mesh():
    """Real candidate-axis sharding: mixed-kind descent on 4 forced host
    devices selects the identical moves as the sequential reference."""
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", _MOVES_SHARDED_SCRIPT],
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "MOVES_SHARDED_OK" in out.stdout


# ------------------------------------------------------ the move algebra


def _grid_masks():
    # two ResNet-style stages + one non-stage site; 2D so share has a
    # last-axis driver structure to respect
    return {"g0b0.relu1": np.ones((3, 4), np.float32),
            "g0b1.relu2": np.ones((2, 4), np.float32),
            "g1b0.relu1": np.ones((2, 6), np.float32),
            "stem.relu": np.ones((4,), np.float32)}


def test_swap_equals_add_back_after_remove():
    masks = _grid_masks()
    flat, _ = M._flatten(masks)
    off, on = np.array([1, 7, 20]), np.array([5])
    flat0 = flat.copy()
    flat0[5] = 0.0                      # make `on` actually inactive
    via_swap = M.Move.swap(off, on).apply_flat(flat0)
    via_pair = M.Move.add_back(on).apply_flat(
        M.Move.remove(off).apply_flat(flat0))
    np.testing.assert_array_equal(via_swap, via_pair)


def test_moves_bill_exactly_minus_drc():
    """Every sampled move nets exactly -drc billable ReLUs (stage_drop up
    to max_remove), for every kind, across a whole descent's mask states."""
    masks = _grid_masks()
    rng = np.random.default_rng(0)
    flat, layout = M._flatten(masks)
    drc, max_remove = 3, 9
    for _ in range(12):
        for kind in M.MOVE_KINDS:
            moves = M.sample_moves(rng, M._unflatten(flat, layout), drc, 4,
                                   kinds=(kind,), max_remove=max_remove)
            for mv in moves:
                d = mv.billable_delta(flat)
                if kind == "stage_drop":
                    assert -max_remove <= d <= -drc, (kind, d)
                else:
                    assert d == -drc, (kind, d)
        # advance the state like a descent step would
        flat = M.sample_moves(rng, M._unflatten(flat, layout), drc, 1,
                              kinds=("share",))[0].apply_flat(flat)
        if int(np.sum(flat > 0.9)) <= max_remove + drc:
            break


def test_moves_never_touch_outside_layout_or_resurrect_active():
    masks = _grid_masks()
    rng = np.random.default_rng(1)
    flat, layout = M._flatten(masks)
    flat[::3] = 0.0                     # a third of the grid already off
    tree = M._unflatten(flat, layout)
    for kind in M.MOVE_KINDS:
        for mv in M.sample_moves(rng, tree, 2, 8, kinds=(kind,),
                                 max_remove=5):
            t = mv.touched()
            assert t.size and t.min() >= 0 and t.max() < flat.size
            assert np.all(flat[mv.off] > 0.9)       # offs were billable
            assert np.all(flat[mv.on] <= 0.5)       # ons were inactive
            assert np.all(flat[mv.tie] > 0.9)       # ties were billable


def test_share_ties_have_billable_driver_and_no_chains():
    masks = _grid_masks()
    rng = np.random.default_rng(2)
    flat, layout = M._flatten(masks)
    for _ in range(8):
        mv = M.sample_moves(rng, M._unflatten(flat, layout), 4, 1,
                            kinds=("share",))[0]
        out = mv.apply_flat(flat)
        for idx in mv.tie.tolist():
            assert out[idx - 1] > 0.9   # driver stays a full ReLU
        flat = out


def test_pi_cost_of_share_tied_mask_bills_driver_relus_only():
    masks = _grid_masks()
    rng = np.random.default_rng(3)
    mv = M.sample_moves(rng, masks, 5, 1, kinds=("share",))[0]
    tied = M.apply_move(masks, mv)
    drivers = M.relu_cost(tied)
    assert drivers == M.count(tied) - M.tied_count(tied)
    got = pi_cost.cost_of_masks(tied, n_nonlinear_layers=len(tied))
    want = pi_cost.cost(drivers, len(tied))
    assert got == want
    # ties are free, gates are kept: cheaper than count, costlier than none
    assert got.online_bytes < pi_cost.cost(M.count(tied), len(tied)).online_bytes


def test_share_forward_is_bitwise_inert_on_binary_masks():
    """_apply_share_ties with an all-binary mask must be the identity on
    the blended output — the pre-move-vocabulary forward, bit for bit."""
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (2, 3, 8)))
    site = linearize.MaskSite(shape=(3, 8))
    mask = (np.arange(24).reshape(3, 8) % 2).astype(np.float32)
    out = linearize.apply_masked_act(x, mask, site)
    want = mask * np.maximum(x, 0.0) + (1.0 - mask) * x
    np.testing.assert_array_equal(np.asarray(out), want.astype(np.float32))


def test_share_forward_reuses_driver_sign():
    """A tied coordinate keeps its gate but gates on the *driver's* sign:
    out = x * H(x_prev) at tied coords, untouched elsewhere."""
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (4, 8)))
    site = linearize.MaskSite(shape=(8,))
    mask = np.ones((8,), np.float32)
    mask[3] = M.TIE
    out = np.asarray(linearize.apply_masked_act(x, mask, site))
    want = np.maximum(x, 0.0)
    want[:, 3] = x[:, 3] * (x[:, 2] > 0)
    np.testing.assert_allclose(out, want, atol=1e-6)


if HAS_HYPOTHESIS:
    @st.composite
    def _flat_and_move(draw):
        n = draw(st.integers(8, 40))
        bits = draw(st.lists(st.integers(0, 1), min_size=n, max_size=n))
        flat = np.asarray(bits, np.float32)
        coords = draw(st.lists(st.integers(0, n - 1), min_size=1,
                               max_size=6, unique=True))
        split = draw(st.integers(0, len(coords)))
        return flat, np.asarray(coords[:split] or coords[:1],
                                dtype=np.int64), \
            np.asarray(coords[split:] or coords[-1:], dtype=np.int64)

    @given(fm=_flat_and_move())
    @settings(max_examples=60, deadline=None)
    def test_swap_decomposition_property(fm):
        """swap(off, on) ≡ add_back(on) ∘ remove(off) on any flat state —
        the move algebra is purely set-valued."""
        flat, off, on = fm
        if set(off.tolist()) & set(on.tolist()):
            return
        via_swap = M.Move.swap(off, on).apply_flat(flat)
        via_pair = M.Move.add_back(on).apply_flat(
            M.Move.remove(off).apply_flat(flat))
        np.testing.assert_array_equal(via_swap, via_pair)

    @given(seed=st.integers(0, 2 ** 20), drc=st.integers(1, 5),
           kind=st.sampled_from(M.MOVE_KINDS))
    @settings(max_examples=60, deadline=None)
    def test_sampled_moves_stay_in_layout_property(seed, drc, kind):
        masks = _grid_masks()
        rng = np.random.default_rng(seed)
        flat, layout = M._flatten(masks)
        flat[rng.random(flat.size) < 0.3] = 0.0
        tree = M._unflatten(flat, layout)
        mv = M.sample_moves(rng, tree, drc, 1, kinds=(kind,),
                            max_remove=2 * drc)[0]
        t = mv.touched()
        assert t.min() >= 0 and t.max() < flat.size
        assert np.all(flat[mv.on] <= 0.5)
        assert -2 * drc <= mv.billable_delta(flat) <= -min(
            drc, int(np.sum(flat > 0.9)))

    @given(seed=st.integers(0, 2 ** 20))
    @settings(max_examples=40, deadline=None)
    def test_share_pi_cost_identity_property(seed):
        masks = _grid_masks()
        rng = np.random.default_rng(seed)
        mv = M.sample_moves(rng, masks, int(rng.integers(1, 6)), 1,
                            kinds=("share",))[0]
        tied = M.apply_move(masks, mv)
        assert pi_cost.cost_of_masks(tied, 4).relus == M.relu_cost(tied)


# ------------------------------------------ engine regression (satellite 3)


def _suffix_ev(model, params, batch, **kw):
    ctx = {"params": params,
           "batch": {k: np.asarray(v) for k, v in batch.items()}}
    return engine.SuffixEvaluator(model.make_suffix_eval_fns(), context=ctx,
                                  **kw)


def test_two_segment_move_never_straddles_sited_chunks(setup):
    """A swap whose rider removals touch a shallower segment than its
    (off, on) exchange must be planned at the *shallower* segment — a
    sited chunk at the deep cut would read the candidate's edited shallow
    mask through the cached prefix."""
    model, params, batch, masks0 = setup
    split = model.make_suffix_eval_fns()
    order_sites = model.site_order()
    shallow, deep = order_sites[0], order_sites[-1]
    flat, layout = M._flatten(masks0)
    site_off = {k: (off, n) for k, off, n, _ in layout}
    so, sn = site_off[shallow]
    do, dn = site_off[deep]
    # candidate 0: pure deep removal; candidate 1: deep swap with a shallow
    # rider; candidate 2: deep removal again (same group as 0 if the
    # straddling candidate were misgrouped, it would split this group)
    moves = [
        M.Move.remove(np.arange(do, do + 4)),
        M.Move.swap(np.array([do + 8, so + 1]), np.array([])),
        M.Move.remove(np.arange(do + 4, do + 8)),
    ]
    ranks = M.move_site_ranks(moves, layout, split.site_segment)
    assert ranks[0] == ranks[2] == split.site_segment[deep]
    assert ranks[1] == split.site_segment[shallow]
    # force suffix mode for every sited chunk — the fallback path would
    # make the straddling check vacuous
    ev = _suffix_ev(model, params, batch, pad_to=3,
                    cost_model=engine.SuffixCostModel(
                        min_prefix_fraction=0.0, min_chunk=1))
    ev.begin_step(masks0)
    order, chunks = engine.plan_sited_chunks(ev, moves, layout,
                                             chunk_size=3)
    assert any(site is not None for site, _, _ in chunks)
    seen = set()
    for site, s, e in chunks:
        sel = order[s:e]
        seen.update(int(i) for i in sel)
        if site is None:
            continue
        seg = split.site_segment[site]
        for i in sel:
            assert ranks[int(i)] == seg, \
                f"candidate {int(i)} (cut {ranks[int(i)]}) landed in a " \
                f"chunk sited at segment {seg}"
    assert seen == {0, 1, 2}
    # and the materialized chunks agree with per-move application
    for chunk in engine.materialize_sited(flat, layout, moves, order,
                                          chunks):
        assert isinstance(chunk, engine.SitedChunk)


def test_begin_step_invalidates_to_shallower_touched_segment(setup):
    """After a two-segment accepted move, the prefix trie must drop every
    entry deeper than the *shallower* touched segment — a prefix cut
    between the two sites reads the shallower site's edited mask."""
    model, params, batch, masks0 = setup
    split = model.make_suffix_eval_fns()
    sites = model.site_order()
    mid, deep = sites[len(sites) // 2], sites[-1]
    segs = split.site_segment
    assert segs[mid] < segs[deep]
    ev = _suffix_ev(model, params, batch, pad_to=4)
    ev.begin_step(masks0)
    rng = np.random.default_rng(0)
    for site in (mid, deep):
        idx = M.sample_removal_indices_within(rng, masks0, 8, 4, [site])
        ev.evaluate(engine.SitedChunk(site, M.materialize_candidates(
            masks0, idx)))
    assert segs[mid] in ev.trie and segs[deep] in ev.trie
    # accept a swap touching BOTH segments: deep (off, on) + mid rider
    flat, layout = M._flatten(masks0)
    site_off = {k: (off, n) for k, off, n, _ in layout}
    mo, _ = site_off[mid]
    do, _ = site_off[deep]
    mv = M.Move.swap(np.array([do + 1, mo + 2]), np.array([]))
    ev.begin_step(M.apply_move(masks0, mv))
    assert segs[deep] not in ev.trie, \
        "deep prefix survived a shallower-site edit"
    assert segs[mid] in ev.trie, \
        "the mid-segment prefix reads only shallower masks and must survive"
