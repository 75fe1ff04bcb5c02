"""Resumable run orchestration (core.runner / launch.sweep).

The contract under test: a BCD run checkpointed after every accepted block
and resumed — after a clean stop, a corrupted newest checkpoint, or a real
SIGKILL — replays bit-identically against an uninterrupted run: same masks,
same step logs (``wall_s`` excepted, which is wall-clock), same finetuned
params.  Plus the shared stage-init warm-start format and the multi-budget
sweep driver built on top.
"""
import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import bcd, engine, linearize, masks as M, runner
from repro.core.snl import finetune as snl_finetune
from repro.data import ImageDatasetCfg, SyntheticImages
from repro.launch import sweep as sweep_lib
from repro.training import checkpoint, optimizer as opt_lib, train as train_lib


# ------------------------------------------------------------ helpers


def _hist_identity(history):
    """Step logs minus wall_s — the deterministic replay identity."""
    out = []
    for h in history:
        d = dataclasses.asdict(h)
        d.pop("wall_s")
        out.append(d)
    return out


def _assert_same_run(a_masks, a_hist, b_masks, b_hist):
    for k in a_masks:
        np.testing.assert_array_equal(a_masks[k], b_masks[k])
    assert _hist_identity(a_hist) == _hist_identity(b_hist)


def _toy_masks(n=48):
    return {"a": np.ones((n // 2,), np.float32),
            "b": np.ones((n // 2,), np.float32)}


def _toy_eval_fn(m):
    # deterministic, coordinate-sensitive accuracy surrogate
    wa = jnp.arange(m["a"].shape[-1], dtype=jnp.float32)
    wb = jnp.arange(m["b"].shape[-1], dtype=jnp.float32)[::-1]
    return 95.0 - 0.02 * (jnp.sum((1 - m["a"]) * wa) +
                          jnp.sum((1 - m["b"]) * wb))


def _toy_eval_acc(m):
    return float(_toy_eval_fn(M.as_device(m)))


def _toy_cfg(masks, steps=4, **kw):
    total = M.count(masks)
    kw.setdefault("b_target", total - 4 * steps)
    kw.setdefault("drc", 4)
    kw.setdefault("rt", 6)
    kw.setdefault("adt", -1.0)       # no early exit: every trial evaluated
    kw.setdefault("chunk_size", 2)
    kw.setdefault("seed", 0)
    return bcd.BCDConfig(**kw)


# ------------------------------------------------------------ rng round-trip


def test_rng_state_roundtrip_through_json():
    rng = np.random.default_rng(123)
    rng.random(37)                                  # advance the stream
    blob = json.dumps(runner.rng_state_to_jsonable(rng))
    rng2 = runner.rng_from_state(json.loads(blob))
    np.testing.assert_array_equal(rng.random(100), rng2.random(100))
    np.testing.assert_array_equal(rng.integers(0, 1 << 62, 10),
                                  rng2.integers(0, 1 << 62, 10))


def test_rng_restore_rejects_foreign_bit_generator():
    state = runner.rng_state_to_jsonable(np.random.default_rng(0))
    state = dict(state, bit_generator="MT19937")
    with pytest.raises(runner.CheckpointError):
        runner.rng_from_state(state)


# ------------------------------------------------------- resume equivalence


def _toy_evaluator(backend):
    if backend == "sequential":
        return engine.SequentialEvaluator(_toy_eval_acc)
    ev = engine.PipelinedEvaluator(_toy_eval_fn, pad_to=2)
    if backend == "depth0":
        ev.prefetch_depth = 0
    elif backend != "pipelined":
        raise AssertionError(backend)
    return ev


@pytest.mark.parametrize("backend", ["sequential", "depth0", "pipelined"])
def test_resume_matches_uninterrupted_across_backends(backend, tmp_path):
    masks = _toy_masks()
    cfg = _toy_cfg(masks, steps=5)

    ref = bcd.run_bcd(masks, cfg, _toy_eval_acc,
                      evaluator=_toy_evaluator(backend))

    d = str(tmp_path / backend)
    part = runner.BCDRunner(cfg, runner.RunnerConfig(ckpt_dir=d, max_steps=2),
                            _toy_eval_acc, evaluator=_toy_evaluator(backend))
    pres = part.run(masks)
    assert part.stopped_early and M.count(pres.masks) > cfg.b_target

    cont = runner.BCDRunner(cfg, runner.RunnerConfig(ckpt_dir=d),
                            _toy_eval_acc, evaluator=_toy_evaluator(backend))
    res = cont.run(masks)
    assert cont.resumed_from == 2 and not cont.stopped_early
    _assert_same_run(ref.masks, ref.history, res.masks, res.history)


def test_typed_move_state_roundtrips_through_resume(tmp_path):
    """Mixed-kind descent under the sensitivity proposal: the proposal
    reads ``move_stats``, so bit-identical resume requires the acceptance
    counters (and the per-step ``move_kind`` logs) to round-trip through
    the checkpoint exactly — not just masks and rng."""
    masks = _toy_masks()
    cfg = _toy_cfg(masks, steps=5, moves=M.MOVE_KINDS,
                   proposal="sensitivity")

    ref = bcd.run_bcd(masks, cfg, _toy_eval_acc)
    assert any(h.move_kind != "remove" for h in ref.history)

    d = str(tmp_path / "moves")
    part = runner.BCDRunner(cfg, runner.RunnerConfig(ckpt_dir=d, max_steps=2),
                            _toy_eval_acc)
    pres = part.run(masks)
    assert part.stopped_early
    # the partial run's counters are a strict prefix of the full run's
    assert sum(v["proposed"] for v in
               pres.move_stats["kinds"].values()) == 2 * cfg.rt

    cont = runner.BCDRunner(cfg, runner.RunnerConfig(ckpt_dir=d),
                            _toy_eval_acc)
    res = cont.run(masks)
    assert cont.resumed_from == 2 and not cont.stopped_early
    _assert_same_run(ref.masks, ref.history, res.masks, res.history)
    assert res.move_stats == ref.move_stats
    assert [h.move_kind for h in res.history] == \
        [h.move_kind for h in ref.history]


def test_resume_with_finetuned_params_roundtrip(tmp_path):
    """Params mutate between outer steps (finetune); they are part of the
    resume state and must round-trip bit-exactly through the checkpoint."""
    from repro.models.resnet import CNN, CNNConfig
    model = CNN(CNNConfig("tiny", 4, 8, ((4, 1, 1),), stem_channels=4))
    data = SyntheticImages(ImageDatasetCfg(n_classes=4, image_size=8,
                                           n_train=64, n_test=32))
    params0 = model.init(jax.random.PRNGKey(0))
    _, loss_fn = train_lib.make_cnn_train_step(model, opt_lib.sgd(lr=1e-2))
    batches_np = data.batches("train", 16)
    batches = lambda i: {k: jnp.asarray(v)
                         for k, v in batches_np(i).items()}
    eval_b = data.train_eval_set(32)
    eval_fn_p = model.make_param_eval_fn(eval_b)
    acc_jit = jax.jit(eval_fn_p)
    masks0 = linearize.init_masks(model.mask_sites())
    cfg = _toy_cfg(masks0, steps=3, drc=16,
                   b_target=M.count(masks0) - 3 * 16, adt=0.5)

    def fresh_ctx():
        holder = {"params": params0}
        eval_acc = lambda m: float(acc_jit(M.as_device(m),
                                           holder["params"]))

        def ft(m):
            holder["params"] = snl_finetune(
                holder["params"], m,
                lambda p, mm, b, soft: loss_fn(p, mm, b, soft),
                batches, steps=4, lr=1e-2)
        return holder, eval_acc, ft

    holder, eval_acc, ft = fresh_ctx()
    ref = bcd.run_bcd(masks0, cfg, eval_acc, finetune=ft)
    ref_params = holder["params"]

    d = str(tmp_path / "ckpt")
    holder, eval_acc, ft = fresh_ctx()
    pio = (lambda: holder["params"],
           lambda p: holder.__setitem__("params", p))
    part = runner.BCDRunner(cfg, runner.RunnerConfig(ckpt_dir=d, max_steps=1),
                            eval_acc, ft, params_io=pio)
    part.run(masks0)
    assert part.stopped_early

    holder, eval_acc, ft = fresh_ctx()     # params reset to params0 —
    pio = (lambda: holder["params"],       # restore must overwrite them
           lambda p: holder.__setitem__("params", p))
    cont = runner.BCDRunner(cfg, runner.RunnerConfig(ckpt_dir=d),
                            eval_acc, ft, params_io=pio)
    res = cont.run(masks0)
    assert cont.resumed_from == 1
    _assert_same_run(ref.masks, ref.history, res.masks, res.history)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), ref_params, holder["params"])


def test_resume_refuses_changed_config(tmp_path):
    masks = _toy_masks()
    cfg = _toy_cfg(masks)
    d = str(tmp_path / "ckpt")
    part = runner.BCDRunner(cfg, runner.RunnerConfig(ckpt_dir=d, max_steps=1),
                            _toy_eval_acc)
    part.run(masks)
    changed = dataclasses.replace(cfg, seed=cfg.seed + 1)
    with pytest.raises(runner.CheckpointError, match="seed"):
        runner.BCDRunner(changed, runner.RunnerConfig(ckpt_dir=d),
                         _toy_eval_acc).run(masks)


# --------------------------------------------- corrupted checkpoint handling


def _run_two_checkpoints(tmp_path):
    masks = _toy_masks()
    cfg = _toy_cfg(masks, steps=4)
    d = str(tmp_path / "ckpt")
    part = runner.BCDRunner(
        cfg, runner.RunnerConfig(ckpt_dir=d, max_steps=2, keep=10),
        _toy_eval_acc)
    part.run(masks)
    assert checkpoint.latest_valid_step(d) == 2
    return masks, cfg, d


def test_corrupted_leaf_falls_back_to_previous_checkpoint(tmp_path):
    masks, cfg, d = _run_two_checkpoints(tmp_path)
    # bit-rot a leaf of the newest checkpoint: same size, flipped bytes
    step_dir = os.path.join(d, "step_00000002")
    leaf = os.path.join(step_dir, "leaf_00000.npy")
    blob = bytearray(open(leaf, "rb").read())
    blob[-1] ^= 0xFF
    open(leaf, "wb").write(bytes(blob))
    assert checkpoint.validate(d, 2, deep=False)       # files all exist...
    assert not checkpoint.validate(d, 2, deep=True)    # ...but hash fails
    assert checkpoint.latest_valid_step(d) == 1
    with pytest.raises(checkpoint.CheckpointError, match="sha256"):
        checkpoint.restore({"masks": masks}, d, 2)
    # the runner resumes from step 1 and still reproduces the full run
    ref = bcd.run_bcd(masks, cfg, _toy_eval_acc)
    cont = runner.BCDRunner(cfg, runner.RunnerConfig(ckpt_dir=d),
                            _toy_eval_acc)
    res = cont.run(masks)
    assert cont.resumed_from == 1
    _assert_same_run(ref.masks, ref.history, res.masks, res.history)


def test_partial_checkpoint_missing_leaf_rejected(tmp_path):
    masks, cfg, d = _run_two_checkpoints(tmp_path)
    os.remove(os.path.join(d, "step_00000002", "leaf_00001.npy"))
    assert not checkpoint.validate(d, 2)
    assert checkpoint.latest_valid_step(d) == 1
    with pytest.raises(checkpoint.CheckpointError):
        checkpoint.restore({"masks": masks}, d, 2)


def test_garbage_manifest_rejected(tmp_path):
    masks, cfg, d = _run_two_checkpoints(tmp_path)
    mf = os.path.join(d, "step_00000002", "manifest.json")
    open(mf, "w").write("{not json")
    assert not checkpoint.validate(d, 2)
    assert checkpoint.latest_valid_step(d) == 1
    with pytest.raises(checkpoint.CheckpointError):
        checkpoint.read_manifest(d, 2)


def test_all_checkpoints_corrupt_is_fresh_start(tmp_path):
    masks, cfg, d = _run_two_checkpoints(tmp_path)
    for s in (1, 2):
        os.remove(os.path.join(d, f"step_{s:08d}", "manifest.json"))
    assert checkpoint.latest_valid_step(d) is None
    with pytest.raises(FileNotFoundError):
        runner.restore_run_state(d, cfg, masks)
    # BCDRunner treats it as a fresh run, not an error
    cont = runner.BCDRunner(cfg, runner.RunnerConfig(ckpt_dir=d),
                            _toy_eval_acc)
    res = cont.run(masks)
    assert cont.resumed_from is None
    ref = bcd.run_bcd(masks, cfg, _toy_eval_acc)
    _assert_same_run(ref.masks, ref.history, res.masks, res.history)


# ------------------------------------------------------------ stage init


def test_stage_init_roundtrip(tmp_path):
    masks = M.threshold({k: np.random.default_rng(0)
                         .random(v.shape).astype(np.float32)
                         for k, v in _toy_masks().items()}, 20)
    params = {"w": np.arange(6, dtype=np.float32).reshape(2, 3),
              "b": np.zeros((3,), np.float32)}
    aux = {"alphas": {"a": np.full((24,), 0.25, np.float32)}}
    init = {"kind": "snl", "masks": masks, "params": params, "aux": aux}
    path = str(tmp_path / "init")
    runner.save_stage_init(path, init)
    assert runner.stage_init_exists(path)

    got = runner.load_stage_init(path, masks, params_template=params,
                                 aux_template=aux)
    assert got["kind"] == "snl"
    for k in masks:
        np.testing.assert_array_equal(got["masks"][k], masks[k])
    np.testing.assert_array_equal(np.asarray(got["params"]["w"]),
                                  params["w"])
    np.testing.assert_array_equal(
        np.asarray(got["aux"]["alphas"]["a"]), aux["alphas"]["a"])
    assert got["meta"]["budget"] == 20
    assert got["meta"]["mask_fingerprint"] == M.fingerprint(masks)

    # aux is optional on load — a sweep that only needs masks+params
    lean = runner.load_stage_init(path, masks, params_template=params)
    assert lean["aux"] is None
    with pytest.raises(runner.CheckpointError):
        runner.load_stage_init(str(tmp_path / "nope"), masks)


def test_snl_and_autorep_results_share_stage_init_shape():
    from repro.core.snl import SNLResult
    from repro.core.autorep import AutoRepResult
    masks = _toy_masks()
    s = SNLResult(params={"w": np.ones(2)}, masks=masks, alphas={},
                  snapshots=[], budget_per_epoch=[], lam_per_epoch=[])
    a = AutoRepResult(params={"w": np.ones(2)}, poly={"p": np.ones(3)},
                      masks=masks, alphas={}, budget_per_epoch=[])
    si, ai = s.stage_init(), a.stage_init()
    assert set(si) == set(ai) == {"kind", "masks", "params", "aux"}
    assert (si["kind"], ai["kind"]) == ("snl", "autorep")


# ------------------------------------------------------------------ sweep


def _sweep_ctx(tmp_path, name="toy"):
    masks = _toy_masks()
    params = {"w": np.arange(4, dtype=np.float32)}
    holder = {"params": params}
    pio = (lambda: holder["params"],
           lambda p: holder.__setitem__("params", p))
    cfg = sweep_lib.SweepConfig(
        budgets=[36, 28], out_dir=str(tmp_path / name), name=name)
    mk = lambda b: _toy_cfg(masks, b_target=b)
    init = {"kind": "snl", "masks": masks, "params": params}
    return masks, holder, pio, cfg, mk, init


def test_sweep_descends_warm_started_and_resumes(tmp_path):
    masks, holder, pio, cfg, mk, init = _sweep_ctx(tmp_path)
    res = sweep_lib.run_sweep(cfg, mk, _toy_eval_acc, init=init,
                              params_io=pio, eval_test=_toy_eval_acc)
    assert res["complete"] and [s["budget"] for s in res["stages"]] == [36, 28]
    assert M.count(res["final_masks"]) == 28
    # each stage's masks are a subset of the previous stage's (warm start)
    assert res["stages"][0]["mask_fingerprint"] != \
        res["stages"][1]["mask_fingerprint"]
    art = json.load(open(res["artifact"]))
    assert art["complete"] and len(art["stages"]) == 2
    assert all("wall_s" not in h for s in art["stages"]
               for h in s["history"])

    # re-run: both stages skip, artifact identical
    res2 = sweep_lib.run_sweep(cfg, mk, _toy_eval_acc, init=init,
                               params_io=pio, eval_test=_toy_eval_acc)
    assert [s["mask_fingerprint"] for s in res2["stages"]] == \
        [s["mask_fingerprint"] for s in res["stages"]]
    assert res2["notes"] == res["notes"]


def test_sweep_interrupted_mid_stage_matches_uninterrupted(tmp_path):
    masks, holder, pio, cfg_a, mk, init = _sweep_ctx(tmp_path, "ref")
    ref = sweep_lib.run_sweep(cfg_a, mk, _toy_eval_acc, init=init,
                              params_io=pio)

    masks, holder, pio, cfg_b, mk, init = _sweep_ctx(tmp_path, "cut")
    cut = sweep_lib.SweepConfig(budgets=cfg_b.budgets,
                                out_dir=cfg_b.out_dir, name=cfg_b.name)
    # interrupt stage 0 mid-run: a runner with max_steps inside the stage
    part = runner.BCDRunner(
        mk(cut.budgets[0]),
        runner.RunnerConfig(
            ckpt_dir=os.path.join(sweep_lib._stage_dir(cut, 0), "ckpt"),
            max_steps=1),
        _toy_eval_acc, params_io=pio)
    runner.save_stage_init(os.path.join(cut.out_dir, "init"), init)
    part.run(masks)
    assert part.stopped_early
    # now run the sweep driver: it must resume the half-done stage
    res = sweep_lib.run_sweep(cut, mk, _toy_eval_acc, init=init,
                              params_io=pio)
    assert [s["mask_fingerprint"] for s in res["stages"]] == \
        [s["mask_fingerprint"] for s in ref["stages"]]
    assert [s["history"] for s in res["stages"]] == \
        [s["history"] for s in ref["stages"]]


def test_overlap_sweep_bit_identical_to_serial(tmp_path):
    """The overlap acceptance criterion: with the reporting tail
    (stage_finetune + stage_eval) running concurrently with the next
    stage's descent, the sweep emits masks, step histories, AND scores
    bit-identical to the serial sweep on the same schedule."""
    def run(name, overlap):
        masks, holder, pio, cfg, mk, init = _sweep_ctx(tmp_path, name)
        cfg = sweep_lib.SweepConfig(
            budgets=cfg.budgets, out_dir=cfg.out_dir, name=name,
            overlap=overlap)
        # a reporting finetune that really transforms params, and a score
        # that depends on both inputs — pure in (params, masks)
        sft = lambda p, m: {"w": p["w"] + np.float32(M.count(m))}
        sev = lambda m, p: _toy_eval_acc(m) + float(np.sum(p["w"]))
        return sweep_lib.run_sweep(cfg, mk, _toy_eval_acc, init=init,
                                   params_io=pio, stage_finetune=sft,
                                   stage_eval=sev)

    serial = run("serial", overlap=False)
    over = run("over", overlap=True)
    assert serial["complete"] and over["complete"]
    for a, b in zip(serial["stages"], over["stages"]):
        assert a["mask_fingerprint"] == b["mask_fingerprint"]
        assert a["history"] == b["history"]
        assert a["test_acc"] == b["test_acc"]
    # the overlapped artifact on disk converged to fully-scored too
    art = json.load(open(over["artifact"]))
    assert art["complete"]
    assert [s.get("test_acc") for s in art["stages"]] == \
        [s["test_acc"] for s in serial["stages"]]


def test_overlap_rejects_impure_eval_test(tmp_path):
    masks, holder, pio, cfg, mk, init = _sweep_ctx(tmp_path)
    cfg = sweep_lib.SweepConfig(budgets=cfg.budgets, out_dir=cfg.out_dir,
                                name=cfg.name, overlap=True)
    with pytest.raises(ValueError, match="stage_eval"):
        sweep_lib.run_sweep(cfg, mk, _toy_eval_acc, init=init,
                            params_io=pio, eval_test=_toy_eval_acc)


def test_resumed_sweep_scores_unscored_stages(tmp_path):
    """A crash after result.json but before the reporting tail leaves a
    completed-but-unscored stage; the resume path must finish scoring it
    rather than shipping an artifact with holes."""
    masks, holder, pio, cfg, mk, init = _sweep_ctx(tmp_path)
    res = sweep_lib.run_sweep(cfg, mk, _toy_eval_acc, init=init,
                              params_io=pio,
                              stage_eval=lambda m, p: _toy_eval_acc(m))
    # simulate the crash window: strip stage 0's score on disk
    rp = os.path.join(sweep_lib._stage_dir(cfg, 0), "result.json")
    stage = json.load(open(rp))
    want = stage.pop("test_acc")
    json.dump(stage, open(rp, "w"))
    res2 = sweep_lib.run_sweep(cfg, mk, _toy_eval_acc, init=init,
                               params_io=pio,
                               stage_eval=lambda m, p: _toy_eval_acc(m))
    assert res2["stages"][0]["test_acc"] == want
    assert json.load(open(rp))["test_acc"] == want
    assert [s["mask_fingerprint"] for s in res2["stages"]] == \
        [s["mask_fingerprint"] for s in res["stages"]]


def test_rescore_does_not_truncate_artifact(tmp_path):
    """The resume re-score path folds its score into the EXISTING artifact:
    when the on-disk artifact already describes more stages than the resume
    loop has revisited, the reporter must patch the stage in place, not
    clobber the artifact with a one-stage partial list."""
    cfg = sweep_lib.SweepConfig(budgets=[36, 28],
                                out_dir=str(tmp_path / "t"), name="t")
    s0 = {"stage": 0, "budget": 36, "mask_fingerprint": "aaa"}
    s1 = {"stage": 1, "budget": 28, "mask_fingerprint": "bbb",
          "test_acc": 9.0}
    os.makedirs(sweep_lib._stage_dir(cfg, 0), exist_ok=True)
    sweep_lib._write_artifact(cfg, [s0, s1], True)

    reporter = sweep_lib._StageReporter(cfg, [s0], None,
                                        lambda m, p: 5.0, None, None)
    reporter.submit(0, s0, _toy_masks(), None)
    reporter.join()
    art = json.load(open(sweep_lib.artifact_path(cfg)))
    assert len(art["stages"]) == 2 and art["complete"]   # not truncated
    assert art["stages"][0]["test_acc"] == 5.0           # score folded in
    assert art["stages"][1] == s1


def test_sweep_validates_schedule(tmp_path):
    masks, holder, pio, cfg, mk, init = _sweep_ctx(tmp_path)
    for bad in ([], [28, 36], [36, 36], [-1], [M.count(masks)]):
        c = sweep_lib.SweepConfig(budgets=bad, out_dir=str(tmp_path / "bad"))
        with pytest.raises(ValueError):
            c.validate(M.count(masks))
    with pytest.raises(ValueError, match="init"):
        sweep_lib.run_sweep(
            sweep_lib.SweepConfig(budgets=[8], out_dir=str(tmp_path / "x")),
            mk, _toy_eval_acc)


# ------------------------------------------------- SIGKILL (the real thing)


_KILL_SCRIPT = r"""
import json, os, sys
import numpy as np
import jax.numpy as jnp
from repro.core import bcd, masks as M
from repro.launch import sweep as sweep_lib

out_dir = sys.argv[1]
masks = {"a": np.ones((24,), np.float32), "b": np.ones((24,), np.float32)}
wa = jnp.arange(24, dtype=jnp.float32)
eval_fn = lambda m: 95.0 - 0.02 * (jnp.sum((1 - m["a"]) * wa) +
                                   jnp.sum((1 - m["b"]) * wa[::-1]))
eval_acc = lambda m: float(eval_fn(M.as_device(m)))
holder = {"params": {"w": np.arange(4, dtype=np.float32)}}
pio = (lambda: holder["params"], lambda p: holder.__setitem__("params", p))
cfg = sweep_lib.SweepConfig(budgets=[36, 28], out_dir=out_dir, name="kill")
mk = lambda b: bcd.BCDConfig(b_target=b, drc=4, rt=6, adt=-1.0,
                             chunk_size=2, seed=0)
init = {"kind": "snl", "masks": masks, "params": holder["params"]}
res = sweep_lib.run_sweep(cfg, mk, eval_acc, init=init, params_io=pio)
print("FPS=" + json.dumps([s["mask_fingerprint"] for s in res["stages"]]))
print("HIST=" + json.dumps([s["history"] for s in res["stages"]]))
"""


def _run_kill_script(out_dir, kill_after=None):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    env.pop(runner.KILL_ENV, None)
    if kill_after is not None:
        env[runner.KILL_ENV] = str(kill_after)
    return subprocess.run([sys.executable, "-c", _KILL_SCRIPT, out_dir],
                          env=env, capture_output=True, text=True,
                          timeout=600)


def test_sweep_survives_sigkill_mid_stage(tmp_path):
    """The acceptance criterion, literally: SIGKILL the sweep process
    mid-stage (stage 0 has 3 steps; kill after 4 accepted blocks = stage 1
    step 1), restart, and the final masks + step logs are bit-identical to
    a never-killed run."""
    ref = _run_kill_script(str(tmp_path / "ref"))
    assert ref.returncode == 0, ref.stderr[-2000:]

    killed = _run_kill_script(str(tmp_path / "res"), kill_after=4)
    assert killed.returncode == -9       # SIGKILL, not a clean exit

    resumed = _run_kill_script(str(tmp_path / "res"))
    assert resumed.returncode == 0, resumed.stderr[-2000:]

    def lines(out):
        return {ln.split("=", 1)[0]: json.loads(ln.split("=", 1)[1])
                for ln in out.stdout.splitlines()
                if ln.startswith(("FPS=", "HIST="))}
    a, b = lines(ref), lines(resumed)
    assert a["FPS"] == b["FPS"]
    assert a["HIST"] == b["HIST"]
