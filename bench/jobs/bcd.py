"""BCD outer steps of the program's optimizer, driven through
``core.bcd.bcd_steps`` with the candidate engine the cell names.

Set-up makes the data, the weights and the starting masks from the seed,
builds one evaluator and one ``bcd_steps`` generator, and drives that same
generator through the cell's warm-up steps (every program the window runs
compiles there).  The window keeps calling it.  Every call into a layer of
the program goes through a host span: the candidate engine (a wrapper that
``bcd_steps`` receives as its evaluator), the base and post-finetune
evaluations, and the finetune callback (``core.snl.finetune``, ended by
``block_until_ready``).

Correctness: for two window steps drawn from the seed, the wrapper keeps
the step's base masks, parameters (and those of the step before), every
candidate's edit and accuracy, the block selected, and the finetune's input
and output.  After the window the plain reference
(``reference/<config reference>.py``) recomputes them, and ``check``
compares:

* ``acc_gap_images``: the widest gap between an accuracy the program
  reported (every candidate, and both evaluations of the step) and the
  reference's, in images of the eval batch;
* ``cand_gap_share``: over all sampled candidates, the summed gap between
  the program's accuracy and the reference's, as a share of the summed
  distance by which the reference moves each candidate from the step's
  base accuracy.  A 100-ReLU edit moves the accuracy by about an image,
  so an engine that ignores the edits, or scores them from a stale
  prefix, stays within an image or two of the reference and passes
  ``acc_gap_images``; here it reads about 1 or more, and round-off far
  less;
* ``select_err``: how many images the best of the program's own candidate
  accuracies lies above that of the block it selected (exact);
* ``finetune_gap``: over the leaves, the widest gap between the norm of the
  program's parameter change over the finetune and the reference's, as a
  share of the reference's (or of the median leaf's, where that is larger);
  leaves whose first reference gradient is under a thousandth of the median
  leaf's are left out (they move by round-off alone);
* ``budget_err``: the billable ReLU count after the step against the count
  before it less ``drc`` (exact).

Each number but ``cand_gap_share`` is the largest over the sampled steps.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import flops as flops_lib


def _key(seed: int):
    """A JAX key from a seed of any size (``--seed`` may exceed 32 bits)."""
    return jax.random.PRNGKey(
        int(np.random.SeedSequence(seed).generate_state(1)[0]))


def start_masks(shapes: Dict[str, tuple], alive: Dict[str, float],
                rng: np.random.Generator) -> Dict[str, np.ndarray]:
    """Per site, exactly ``round(frac * size)`` live coordinates drawn at
    random; ``frac`` is the ``alive`` entry whose key is the longest prefix
    of the site name (``"*"`` matches every site)."""
    out = {}
    for site, shape in shapes.items():
        keys = [k for k in alive if k == "*" or site.startswith(k)]
        frac = alive[max(keys, key=lambda k: (k != "*", len(k)))]
        size = int(np.prod(shape))
        m = np.zeros(size, np.float32)
        m[rng.permutation(size)[:int(round(frac * size))]] = 1.0
        out[site] = m.reshape(shape)
    return out


def _flat(masks: Dict[str, np.ndarray], keys: List[str]) -> np.ndarray:
    return np.concatenate([np.asarray(masks[k], np.float32).reshape(-1)
                           for k in keys])


class _Recorded:
    """What one sampled step hands to the check."""

    def __init__(self, base_flat: np.ndarray, params, prev_params):
        self.base_flat = base_flat
        self.params = params
        self.prev_params = prev_params   # at the start of the step before
        self.ref = None                  # the reference's outputs, once run
        self.evals: List[float] = []
        self.cands: List[tuple] = []     # (flat indices, values, accuracy)
        self.pending: List[list] = []    # per staged chunk: [(idx, vals)]
        self.ft: Optional[dict] = None
        self.selected: Optional[tuple] = None


class Engine:
    """The evaluator ``bcd_steps`` receives: the cell's engine, with a host
    span around every call, candidate counters, and (on sampled steps) a
    record of each candidate's edit and answer."""

    def __init__(self, inner, job: "Job"):
        self._inner = inner
        self._job = job

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def begin_step(self, base_masks) -> None:
        with self._job.rec.span("engine.begin_step"):
            self._inner.begin_step(base_masks)

    def _count(self, item) -> None:
        job = self._job
        site = getattr(item, "site", None)
        stacked = getattr(item, "stacked", item)
        n = len(next(iter(stacked.values())))
        job.cur["n_cand"] += n
        if site is not None:
            job.cur["n_suffix"] += n
            job.cur["flops"] += n * job.suffix_flops[job.segment[site]]
        elif job.tracing:
            job.cur["flops"] += sum(job.suffix_flops[s] for s in
                                    job.earliest_segments(stacked))
        if job.recorded is not None:
            job.recorded.pending.append(job.edits(stacked))

    def stage(self, item):
        self._count(item)
        with self._job.rec.span("engine.stage"):
            return self._inner.stage(item)

    def evaluate_staged(self, staged):
        with self._job.rec.span("engine.evaluate"):
            accs = self._inner.evaluate_staged(staged)
        self._answers(accs)
        return accs

    def evaluate(self, item):
        self._count(item)
        with self._job.rec.span("engine.evaluate"):
            accs = self._inner.evaluate(item)
        self._answers(accs)
        return accs

    def _answers(self, accs) -> None:
        r = self._job.recorded
        if r is not None:
            edits = r.pending.pop(0)
            r.cands.extend((i, v, float(a)) for (i, v), a in
                           zip(edits, np.asarray(accs)))


class Job:
    def __init__(self, ctx):
        from repro.core import bcd, snl
        from repro.launch import sweep
        from repro.models.resnet import CNN, CNNConfig
        from repro.training import train as train_lib

        self.ctx, self.rec, self.tracing = ctx, ctx.rec, ctx.tracing
        cfg, wl = ctx.config, ctx.workload
        self.cfg, self.wl = cfg, wl
        self.snl = snl
        ref = ctx.reference
        self.net = ref.Net(cfg)
        self.model = CNN(CNNConfig(
            cfg["model_name"], cfg["n_classes"], cfg["image_size"],
            tuple(tuple(s) for s in cfg["stages"]), cfg["stem_channels"]))
        shapes = ref.site_shapes(cfg)
        prog = {k: s.shape for k, s in self.model.mask_sites().items()}
        if prog != shapes:
            raise RuntimeError(f"program mask sites {prog} differ from the "
                               f"reference's {shapes}")
        self.shapes = shapes
        self.keys = sorted(shapes)
        self.offsets = np.cumsum([0] + [int(np.prod(shapes[k]))
                                        for k in self.keys])
        self.site_order = list(shapes)            # forward order
        self.segment = ref.site_segment(cfg)
        self.suffix_flops = {
            k: v * cfg["eval_batch"]
            for k, v in flops_lib.cnn_suffix(cfg).items()}
        rng = np.random.default_rng(ctx.seed)
        self.check_steps = sorted({int(rng.integers(lo, hi))
                                   for lo, hi in wl["check_steps"]})

        # data from the configuration's own seed (every run seed sees the
        # same images, so the programs that hold the eval batch as a
        # constant come from the persistent cache); weights, masks,
        # candidates from the run seed; all on the device
        E, B = cfg["eval_batch"], cfg["finetune_batch"]
        masks0 = start_masks(shapes, wl["alive"], rng)

        R = cfg["readout_images"]

        @jax.jit
        def make(kd, kw, masks):
            images, labels = ref.make_data(cfg, kd, cfg["n_train"])
            params = self.net.fit_readout(ref.init_convs(cfg, kw), masks,
                                          images[E:E + R], labels[E:E + R])
            return images, labels, params
        self.images, self.labels, params = jax.block_until_ready(make(
            _key(cfg["data_seed"]), _key(ctx.seed),
            {k: jnp.asarray(v) for k, v in masks0.items()}))
        self.n_batches = cfg["n_train"] // B
        self._slice = jax.jit(lambda a, j: jax.lax.dynamic_slice_in_dim(
            a, j * B, B))
        self.eval_b = {"images": self.images[:E], "labels": self.labels[:E]}
        self.holder = {"params": params}

        def sloss(p, a, batch, soft):
            logits = self.model.forward(p, a, batch["images"], soft=soft)
            return train_lib.cross_entropy(logits, batch["labels"]), 0.0
        self.sloss = sloss

        inner, eval_acc, self.set_ctx = sweep.make_bcd_evaluator(
            wl["engine"], self.model, self.eval_b, self.holder,
            chunk_size=wl["chunk_size"], rt=wl["rt"])
        self._eval_acc = eval_acc
        self.engine = Engine(inner, self)
        self.bcfg = bcd.BCDConfig(
            b_target=wl["b_target"], drc=wl["drc"], rt=wl["rt"],
            adt=wl["adt"], seed=ctx.seed, chunk_size=wl["chunk_size"],
            moves=tuple(wl["moves"]), proposal=wl["proposal"])
        self.seg_order = list(dict.fromkeys(self.segment[k]
                                            for k in self.site_order))
        self.site_of = np.repeat(np.arange(len(self.keys)),
                                 np.diff(self.offsets))
        self.state = bcd.init_state(masks0, self.bcfg)
        self.gen = bcd.bcd_steps(self.state, self.bcfg, self.eval_acc,
                                 self.finetune, evaluator=self.engine)
        self.ft_calls = 0
        self.steps: List[dict] = []
        self.recorded: Optional[_Recorded] = None
        self.records: List[_Recorded] = []
        self.prev_params = params
        self.cur = self._new_step()
        for _ in range(wl["warmup_steps"]):
            self._step()
        self.steps.clear()

    # --------------------------------------------------------- callbacks

    def batches(self, i: int) -> dict:
        j = i % self.n_batches
        return {"images": self._slice(self.images, j),
                "labels": self._slice(self.labels, j)}

    def eval_acc(self, masks) -> float:
        with self.rec.span("eval"):
            acc = self._eval_acc(masks)
        if self.recorded is not None:
            self.recorded.evals.append(acc)
        return acc

    def finetune(self, masks) -> None:
        steps = self.cfg["finetune_steps"]
        start = self.ft_calls * steps
        self.ft_calls += 1
        before = self.holder["params"]
        with self.rec.span("finetune"):
            after = jax.block_until_ready(self.snl.finetune(
                before, masks, self.sloss, self.batches, steps=steps,
                lr=self.cfg["finetune_lr"], start_step=start))
        if self.recorded is not None:
            self.recorded.ft = {"before": before, "after": after,
                                "start": start,
                                "masks": _flat(masks, self.keys)}
        self.holder["params"] = after
        with self.rec.span("engine.set_context"):
            self.set_ctx(after)

    # ------------------------------------------------------------- steps

    def _new_step(self) -> dict:
        return {"n_cand": 0, "n_suffix": 0, "flops": 0.0}

    def edits(self, stacked) -> List[tuple]:
        """Each stacked candidate's changed coordinates against the step's
        base masks: ``[(flat indices, new values)]``."""
        flat = np.concatenate(
            [np.asarray(stacked[k], np.float32).reshape(
                len(stacked[k]), -1) for k in self.keys], axis=1)
        base = self.recorded.base_flat
        out = []
        for row in flat:
            idx = np.flatnonzero(row != base)
            out.append((idx, row[idx]))
        return out

    def earliest_segments(self, stacked) -> List[str]:
        """The segment of each candidate's earliest edited site."""
        base = self._base_now
        n = len(next(iter(stacked.values())))
        seg = [None] * n
        for site in self.site_order:
            todo = [i for i in range(n) if seg[i] is None]
            if not todo:
                break
            diff = np.any(np.asarray(stacked[site])[todo].reshape(
                len(todo), -1) != base[site].reshape(1, -1), axis=1)
            for i, d in zip(todo, diff):
                if d:
                    seg[i] = self.segment[site]
        return [s if s is not None else "head" for s in seg]

    def _step(self) -> dict:
        k = len(self.steps)
        sampled = k in self.check_steps and self.recording
        base = self.state.masks
        self._base_now = base
        params, prev = self.holder["params"], self.prev_params
        self.prev_params = params
        if sampled:
            self.recorded = _Recorded(_flat(base, self.keys), params, prev)
        self.cur = self._new_step()
        t0 = time.perf_counter()
        n_spans = len(self.rec.spans)
        with self.rec.span("step"):
            try:
                next(self.gen)
            except StopIteration:
                raise RuntimeError(
                    "the BCD schedule ended inside the window: lower the "
                    "cell's b_target") from None
        t1 = time.perf_counter()
        spans = self.rec.spans[n_spans:]
        in_spans = lambda pred: sum(e - s for n, s, e in spans if pred(n))
        rec = dict(self.cur, t0=t0, t1=t1,
                   engine_s=in_spans(lambda n: n.startswith("engine.")),
                   finetune_s=in_spans(lambda n: n == "finetune"),
                   eval_s=in_spans(lambda n: n == "eval"))
        if sampled:
            r = self.recorded
            r.new_flat = _flat(self.state.masks, self.keys)
            idx = np.flatnonzero(r.new_flat != r.base_flat)
            r.selected = (idx, r.new_flat[idx])
            self.records.append(r)
            self.recorded = None
        self.steps.append(rec)
        return rec

    recording = False

    def run(self, win) -> None:
        self.recording = True
        while win.running():
            self._step()
        self.recording = False

    def after_window(self) -> None:
        """Print how the window's steps spread (for finding far-off runs)."""
        q = lambda xs: np.percentile(xs, [0, 50, 100]).round(4).tolist()
        col = lambda key: [s[key] for s in self.steps]
        print(f"[bcd] {len(self.steps)} steps; min/median/max s: step "
              f"{q([s['t1'] - s['t0'] for s in self.steps])}, finetune "
              f"{q(col('finetune_s'))}, engine {q(col('engine_s'))}, "
              f"eval {q(col('eval_s'))}", flush=True)

    def end_to_end(self, win) -> dict:
        return {"bcd_step_s": win.elapsed / len(self.steps)}

    def counts(self):
        return len(self.steps), 0

    def release(self) -> None:
        """Free the program's state before the reference runs."""
        self.gen.close()
        self.gen = self.engine = self.set_ctx = self._eval_acc = None
        self.holder = self.prev_params = None

    # ------------------------------------------------------------- check

    def _tree(self, flat: np.ndarray) -> Dict[str, jnp.ndarray]:
        return {k: jnp.asarray(flat[self.offsets[i]:self.offsets[i + 1]]
                               .reshape(self.shapes[k]))
                for i, k in enumerate(self.keys)}

    def _ft_batches(self, start: int, half: bool = False) -> List[tuple]:
        out = []
        for i in range(self.cfg["finetune_steps"]):
            b = self.batches(start + i)
            n = len(b["labels"]) // 2 if half else len(b["labels"])
            out.append((b["images"][:n], b["labels"][:n]))
        return out

    def _count_right(self, params, flat, dtype=jnp.float32,
                     half: bool = False) -> float:
        """Images of the eval batch the reference in ``dtype`` classifies
        right; with ``half`` over its first half only, scaled to the
        whole batch (the mean taken over the rest)."""
        images, labels = self.eval_b["images"], self.eval_b["labels"]
        n = len(labels) // 2 if half else len(labels)
        right = int(self.net.correct(params, self._tree(flat), images[:n],
                                     labels[:n], dtype=dtype))
        return right * len(labels) / n

    def readings(self, r: _Recorded, prog: dict, ref: dict) -> dict:
        """The compared numbers of one step from the program's (or a
        stand-in's) outputs ``prog`` and the reference's ``ref``."""
        ref_after = self._count_right(prog["params_after"], r.ft["masks"])
        cand_gap = np.abs(prog["cands"] - ref["cands"])
        acc_gap = max(float(np.max(cand_gap)),
                      abs(prog["base"] - ref["base"]),
                      abs(prog["after"] - ref_after))
        budget = lambda flat: int(np.sum(flat > 0.9))
        return {"acc_gap_images": acc_gap,
                "cand_gap_sum": float(np.sum(cand_gap)),
                "cand_move_sum": float(np.sum(np.abs(ref["cands"]
                                                     - ref["base"]))),
                "select_err": float(np.max(prog["cands"])
                                    - prog["cands"][prog["selected"]]),
                "finetune_gap": self.update_gap(r, prog["params_after"],
                                                ref["params_after"],
                                                ref["grad"]),
                "budget_err": float(abs(budget(r.new_flat) - (
                    budget(r.base_flat) - self.bcfg.drc)))}

    def update_gap(self, r: _Recorded, after, ref_after, grad) -> float:
        before = jax.tree.leaves(r.ft["before"])
        norm = lambda x: float(jnp.linalg.norm(x.ravel()))
        d_prog = np.array([norm(a - b) for a, b in
                           zip(jax.tree.leaves(after), before)])
        d_ref = np.array([norm(a - b) for a, b in
                          zip(jax.tree.leaves(ref_after), before)])
        g = np.array([norm(x) for x in jax.tree.leaves(grad)])
        keep = g >= 1e-3 * np.median(g)
        d_prog, d_ref = d_prog[keep], d_ref[keep]
        gaps = np.abs(d_prog - d_ref) / np.maximum(d_ref, np.median(d_ref))
        names = [jax.tree_util.keystr(p) for p, _ in
                 jax.tree_util.tree_flatten_with_path(grad)[0]]
        self.worst_leaf = np.array(names)[keep][int(np.argmax(gaps))]
        return float(np.max(gaps))

    def _selected_index(self, r: _Recorded) -> int:
        idx, vals = r.selected
        for i, (ci, cv, _) in enumerate(r.cands):
            if np.array_equal(ci, idx) and np.array_equal(cv, vals):
                return i
        raise RuntimeError("the accepted block is none of the candidates "
                           "the engine evaluated")

    def stale_segments(self, idx: np.ndarray) -> set:
        """The segments whose output an engine keeps for a candidate that
        edits the flat coordinates ``idx``: every segment before the
        earliest one it edits (the prefix), and the stem's convolution,
        which the program folds once per context on every path."""
        first = min(self.seg_order.index(self.segment[self.keys[i]])
                    for i in np.unique(self.site_of[idx]))
        return set(self.seg_order[:first]) | {"stem"}

    def reference_cands(self, r: _Recorded, dtype=jnp.float32,
                        half: bool = False,
                        stale: bool = False) -> np.ndarray:
        """The reference's count of each candidate; ``stale`` scores it
        with the parameters of the step before in the segments that an
        engine keeps (``stale_segments``)."""
        cands = []
        for idx, vals, _ in r.cands:
            m = r.base_flat.copy()
            m[idx] = vals
            params = r.params
            if stale:
                old = self.stale_segments(idx)
                params = {k: r.prev_params[k] if k in old else v
                          for k, v in r.params.items()}
            cands.append(self._count_right(params, m, dtype, half))
        return np.array(cands)

    def reference_outputs(self, r: _Recorded, dtype=jnp.float32,
                          half: bool = False) -> dict:
        """What the reference in ``dtype`` gives for the step's inputs;
        ``half`` leaves half of every batch out."""
        after, grad = self.net.finetune(
            r.ft["before"], self._tree(r.ft["masks"]),
            self._ft_batches(r.ft["start"], half), self.cfg["finetune_lr"],
            dtype=dtype)
        cands = self.reference_cands(r, dtype, half)
        return {"base": self._count_right(r.params, r.base_flat, dtype,
                                          half),
                "cands": cands,
                "after": self._count_right(after, r.ft["masks"], dtype,
                                           half),
                "params_after": after, "grad": grad,
                "selected": int(np.argmax(cands))}

    def program_outputs(self, r: _Recorded) -> dict:
        E = self.cfg["eval_batch"]
        if len(r.evals) != 2 or r.ft is None:
            raise RuntimeError(f"sampled step recorded {len(r.evals)} "
                               "evaluations and no finetune")
        return {"base": r.evals[0] * E / 100.0,
                "cands": np.array([a * E / 100.0 for _, _, a in r.cands]),
                "after": r.evals[1] * E / 100.0,
                "params_after": r.ft["after"],
                "selected": self._selected_index(r)}

    #: stand-ins for the program: the control, and planted faults
    STAND_INS = ("control", "half_batch", "answer_altered", "edit_ignored",
                 "stale_prefix")

    def step_readings(self, stand_in: Optional[str] = None) -> List[dict]:
        """Readings of every sampled step: the program against the
        reference, or a stand-in in the program's place: the reference in
        bfloat16 (``control``), the reference with half of every batch left
        out (``half_batch``), the program's outputs with one answer altered
        by 10 points (``answer_altered``) or with every candidate scored at
        the step's base masks (``edit_ignored``: its own base evaluation),
        or the reference scoring each candidate from a prefix of the step
        before (``stale_prefix``)."""
        out = []
        for r in self.records:
            if r.ref is None:
                r.ref = self.reference_outputs(r)
            ref = r.ref
            if stand_in is None:
                prog = self.program_outputs(r)
            elif stand_in == "control":
                prog = self.reference_outputs(r, jnp.bfloat16)
            elif stand_in == "half_batch":
                prog = self.reference_outputs(r, half=True)
            elif stand_in == "answer_altered":
                prog = self.program_outputs(r)
                prog["cands"][len(prog["cands"]) // 2] += \
                    0.1 * self.cfg["eval_batch"]
            elif stand_in == "edit_ignored":
                prog = self.program_outputs(r)
                prog["cands"][:] = prog["base"]
            elif stand_in == "stale_prefix":
                prog = dict(self.program_outputs(r),
                            cands=self.reference_cands(r, stale=True))
            else:
                raise ValueError(f"unknown stand-in {stand_in!r}")
            out.append(self.readings(r, prog, ref))
        return out

    def check(self, stand_in: Optional[str] = None) -> dict:
        limits = self.wl["check"]
        if len(self.records) != len(self.check_steps):
            return {"sampled_steps_missing": {
                "value": len(self.check_steps) - len(self.records),
                "limit": 0}}
        rows = self.step_readings(stand_in)
        total = lambda k: sum(row[k] for row in rows)
        gap, move = total("cand_gap_sum"), total("cand_move_sum")
        value = {k: max(row[k] for row in rows) for k in rows[0]}
        value["cand_gap_share"] = gap / move if move else (
            0.0 if gap == 0 else float("inf"))
        return {k: {"value": value[k], "limit": limits[k]} for k in limits}
