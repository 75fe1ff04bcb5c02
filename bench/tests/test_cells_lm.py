"""Whole runs of ``bench/run.py`` on a tiny LM cell on the CPU (the
``bcd_lm`` job: latent attention, held experts, labels in the batch),
through a copy of the benchmark with the cell added as new files and
entries only: a sound run comes out correct, and the timed path broken
underneath in each way an LM BCD cell can break does not.  Also the LM
FLOPs against a hand count."""
import json
import os
import shutil
import types

import jax
import numpy as np
import pytest

from conftest import ROOT
from test_cells import run_cell

TINY_LM = {
    "model_name": "tiny-mla-moe", "source": "test fixture", "job": "bcd_lm",
    "reference": "mla_moe", "program_config": "deepseek_v2_lite",
    "first_k_dense_replace": 1, "hidden_size": 64, "intermediate_size": 96,
    "kv_lora_rank": 32, "moe_intermediate_size": 32, "n_routed_experts": 4,
    "n_shared_experts": 2, "norm_topk_prob": False,
    "num_attention_heads": 4, "num_experts_per_tok": 6,
    "num_hidden_layers": 4, "num_key_value_heads": 4,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "rms_norm_eps": 1e-6,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 1,
    "tie_word_embeddings": False, "v_head_dim": 16, "vocab_size": 64,
    "router_experts": 16, "expert_offset": 4, "dtype": "float32",
    "reference_precision": "highest", "data_seed": 7, "eval_batch": 2,
    "eval_len": 64, "n_train": 8, "finetune_batch": 1, "finetune_len": 16,
    "finetune_steps": 3, "finetune_lr": 0.1, "label_share": [30.0, 90.0],
    "reduced": []}
TINY_BCD = {
    "traffic": "experts", "engine": "suffix", "alive": {"*": 0.0, "s0": 1.0},
    "drc": 8, "rt": 6, "chunk_size": 4, "adt": -1000.0,
    "moves": ["remove"], "proposal": "uniform", "b_target": 0,
    "warmup_steps": 1, "check_steps": [[0, 1], [1, 3]],
    "check": {"acc_gap_tokens": 0.5, "cand_gap_share": 0.05,
              "select_err": 0, "finetune_gap": 0.01, "budget_err": 0}}
CELL = "bcd.tiny_lm.experts"


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A copy of the benchmark with the tiny LM cell added as files and
    entries only."""
    root = tmp_path_factory.mktemp("checkout_lm")
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    (root / "bench" / "configs" / "tiny_lm.json").write_text(
        json.dumps(TINY_LM))
    (root / "bench" / "workloads" / f"{CELL}.json").write_text(
        json.dumps(TINY_BCD))
    b["configs"].append({"name": "tiny_lm", "source": "test fixture",
                         "file": "bench/configs/tiny_lm.json",
                         "reduced": [], "why": "test fixture"})
    b["workloads"].append({"name": CELL, "config": "tiny_lm",
                           "traffic": "experts", "chips": 1,
                           "why": "test fixture"})
    for m in b["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    return root


def test_sound_lm_run_is_correct(checkout, monkeypatch, capsys):
    res = run_cell(checkout, CELL, monkeypatch, capsys, seed=3000000031,
                   seconds=2.0)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"bcd_step_s", "setup_s"}
    assert res["attempted"] >= 3 and res["failed"] == 0


def _finetune_unchanged(monkeypatch):
    from repro.core import snl
    monkeypatch.setattr(snl, "finetune", lambda params, *a, **k: params)


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


def _stale_carry(monkeypatch):
    """The prefix trie keeps the carries of the parameters before the
    finetune."""
    from repro.core import engine

    def set_context(self, context):
        self._inner.set_context(self._with_pre(context))
    monkeypatch.setattr(engine.SuffixEvaluator, "set_context", set_context)


@pytest.mark.parametrize("fault", [_finetune_unchanged, _edit_ignored,
                                   _stale_carry])
def test_broken_lm_run_is_not_correct(checkout, monkeypatch, capsys, fault):
    fault(monkeypatch)
    res = run_cell(checkout, CELL, monkeypatch, capsys, seed=3000000031,
                   seconds=2.0)
    assert not res["correct"], res["checks"]


def test_lm_stand_ins_are_not_correct_where_the_program_is(checkout):
    """The control (the reference in bfloat16 in the program's place) and
    every stand-in fail a compared number that the program passes."""
    from bench.lib import spans, spec as spec_lib, window
    spec = spec_lib.Spec.load(str(checkout))
    cell = spec.cell(CELL)
    cfg = spec.config_file(cell["config"])
    ctx = types.SimpleNamespace(
        name=CELL, cell=cell, config=cfg, workload=spec.workload_file(CELL),
        seed=3000000033, seconds=2.0, tracing=False, rec=spans.Recorder(),
        spec=spec, reference=spec.reference(cfg["reference"]))
    job = spec.job(cfg["job"]).Job(ctx)
    with window.Window(ctx.seconds) as win:
        job.run(win)
    job.after_window()
    job.release()
    ok = lambda checks: all(c["value"] <= c["limit"]
                            for c in checks.values())
    assert ok(job.check())
    for stand_in in job.STAND_INS:
        assert not ok(job.check(stand_in)), stand_in
    jax.clear_caches()


def test_lm_flops_against_a_hand_count():
    from bench.lib import lm_flops
    c = dict(TINY_LM, num_hidden_layers=3)
    d, H = 64, 4
    # q 64x(4x24), [c_kv; k_pe] 64x40, [k_nope; v] 32x(4x32), out 64x64
    proj = d * H * 24 + d * 40 + 32 * H * 32 + H * 16 * d
    seq = 32
    mla = 2 * proj + 2 * H * (24 + 16) * (seq + 1) / 2
    assert lm_flops.mla_token(c, seq) == mla
    dense = mla + 2 * 3 * d * 96
    rpt = 6 * 4 / 16          # uniform: top-6 of 16, 4 held
    moe = mla + 2 * d * 16 + rpt * 2 * 3 * d * 32 + 2 * 3 * d * 64
    head = 2 * d * 64
    assert lm_flops.segments(c, seq) == [("layer0", dense), ("layer1", moe),
                                         ("layer2", moe), ("head", head)]
    assert lm_flops.suffix_token(c, seq)["layer1"] == 2 * moe + head
    assert lm_flops.forward_token(c, seq, [1.0, 2.0]) == pytest.approx(
        dense + 2 * (moe - rpt * 2 * 3 * d * 32) + 3 * 2 * 3 * d * 32 + head)
    f, b = lm_flops.experts_call(c, 100)
    assert f == 2 * 100 * 3 * d * 32
    assert b == 4 * (3 * 4 * d * 32 + 2 * 100 * d)
