"""Whole runs of ``bench/run.py`` at a tiny size on the CPU, past its look
for a chip: a cell defined only here (new files plus ``BENCHMARK.json``
entries in a copy of the benchmark, no edit to an existing file), a sound
run that comes out correct, and the timed path broken underneath in each
way a BCD cell can break, which must come out not correct."""
import importlib.util
import json
import os
import shutil

import jax
import numpy as np
import pytest

from conftest import ROOT

TINY_CNN = {
    "model_name": "tiny-cnn", "source": "test fixture", "job": "bcd",
    "reference": "cnn", "n_classes": 4, "image_size": 16,
    "stem_channels": 4, "stages": [[4, 1, 1], [32, 1, 2]],
    "dtype": "float32", "n_train": 256, "data_seed": 5, "noise": 1.5,
    "eval_batch": 64, "readout_images": 64, "reference_precision": "highest",
    "finetune_batch": 16, "finetune_steps": 3, "finetune_lr": 0.1,
    "reduced": []}
TINY_BCD = {
    "traffic": "deep", "engine": "suffix", "alive": {"*": 0.0, "g1": 1.0},
    "drc": 8, "rt": 6, "chunk_size": 4, "adt": -1000.0,
    "moves": ["remove"], "proposal": "uniform", "b_target": 0,
    "warmup_steps": 1, "check_steps": [[0, 1], [1, 3]],
    "check": {"acc_gap_images": 0.5, "cand_gap_share": 0.05,
              "select_err": 0, "finetune_gap": 0.01, "budget_err": 0}}
CELLS = {"bcd.tiny_cnn.deep": ("tiny_cnn", TINY_CNN, TINY_BCD)}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A copy of the benchmark with the tiny cells added as files and
    entries only."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    for name, (cfg_name, cfg, wl) in CELLS.items():
        assert not (root / "bench" / "workloads" / f"{name}.json").exists()
        (root / "bench" / "configs" / f"{cfg_name}.json").write_text(
            json.dumps(cfg))
        (root / "bench" / "workloads" / f"{name}.json").write_text(
            json.dumps(wl))
        b["configs"].append({"name": cfg_name, "source": "test fixture",
                             "file": f"bench/configs/{cfg_name}.json",
                             "reduced": [], "why": "test fixture"})
        b["workloads"].append({"name": name, "config": cfg_name,
                               "traffic": wl["traffic"], "chips": 1,
                               "why": "test fixture"})
        for m in b["end_to_end"]:
            if "workloads" in m:
                m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    return root


def run_cell(root, name, monkeypatch, capsys, seed=3000000021,
             seconds=6.0):
    """One run of the copy's ``bench/run.py``; returns its result line."""
    from bench.lib import device
    monkeypatch.setattr(device, "require", lambda chips: {
        "platform": "cpu", "kind": "cpu", "count": chips})
    monkeypatch.setattr(device, "memory_peak_bytes", lambda chips: 0)
    spec = importlib.util.spec_from_file_location(
        "bench_run_copy", os.path.join(root, "bench", "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    assert run.main(["--workload", name, "--seed", str(seed), "--seconds",
                     str(seconds), "--trace", "0"]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


def test_sound_bcd_run_is_correct(checkout, monkeypatch, capsys):
    res = run_cell(checkout, "bcd.tiny_cnn.deep", monkeypatch, capsys)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"bcd_step_s", "setup_s"}
    assert res["attempted"] >= 3 and res["failed"] == 0


def _finetune_unchanged(monkeypatch):
    from repro.core import snl
    monkeypatch.setattr(snl, "finetune", lambda params, *a, **k: params)


def _finetune_half_batch(monkeypatch):
    from repro.core import snl
    real = snl.finetune

    def half(params, masks, loss_fn, batches, **kw):
        def b(i):
            full = batches(i)
            n = len(full["labels"]) // 2
            return {k: v[:n] for k, v in full.items()}
        return real(params, masks, loss_fn, b, **kw)
    monkeypatch.setattr(snl, "finetune", half)


def _answer_altered(monkeypatch):
    from repro.core import engine
    real = engine.PipelinedEvaluator.evaluate_staged

    def altered(self, staged):
        accs = np.array(real(self, staged), dtype=np.float64)
        accs[0] += 10.0
        return accs
    monkeypatch.setattr(engine.PipelinedEvaluator, "evaluate_staged",
                        altered)


def _edit_ignored(monkeypatch):
    """The engine scores every candidate at the step's base masks."""
    from repro.core import engine
    real = engine.SuffixEvaluator.stage

    def stage(self, item):
        site, stacked = (item.site, item.stacked) \
            if isinstance(item, engine.SitedChunk) else (None, item)
        n = len(next(iter(stacked.values())))
        base = {k: np.repeat(self._base_masks[k][None], n, axis=0)
                for k in stacked}
        return real(self, engine.SitedChunk(site, base))
    monkeypatch.setattr(engine.SuffixEvaluator, "stage", stage)


def _stale_prefix(monkeypatch):
    """The prefix trie keeps the prefixes of the parameters before the
    finetune."""
    from repro.core import engine

    def set_context(self, context):
        self._inner.set_context(self._with_pre(context))
    monkeypatch.setattr(engine.SuffixEvaluator, "set_context", set_context)


@pytest.mark.parametrize("fault", [_finetune_unchanged, _finetune_half_batch,
                                   _answer_altered, _edit_ignored,
                                   _stale_prefix])
def test_broken_bcd_run_is_not_correct(checkout, monkeypatch, capsys,
                                       fault):
    fault(monkeypatch)
    res = run_cell(checkout, "bcd.tiny_cnn.deep", monkeypatch, capsys)
    assert not res["correct"], res["checks"]


def test_control_is_not_correct_where_the_program_is(checkout):
    """The control (the reference a precision lower, in the program's
    place) and every planted stand-in fail a compared number that the
    program passes."""
    import types
    from bench.lib import spans, spec as spec_lib, window
    spec = spec_lib.Spec.load(str(checkout))
    for name in CELLS:
        cell = spec.cell(name)
        cfg = spec.config_file(cell["config"])
        ctx = types.SimpleNamespace(
            name=name, cell=cell, config=cfg,
            workload=spec.workload_file(name), seed=3000000023,
            seconds=6.0, tracing=False, rec=spans.Recorder(), spec=spec,
            reference=spec.reference(cfg["reference"]))
        job = spec.job(cfg["job"]).Job(ctx)
        with window.Window(ctx.seconds) as win:
            job.run(win)
        job.after_window()
        job.release()
        ok = lambda checks: all(c["value"] <= c["limit"]
                                for c in checks.values())
        assert ok(job.check()), name
        for stand_in in job.STAND_INS:
            assert not ok(job.check(stand_in)), (name, stand_in)
        jax.clear_caches()
