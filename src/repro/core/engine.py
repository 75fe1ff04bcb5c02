"""Candidate-evaluation engine for BCD (Alg. 2's hot path).

One BCD outer step evaluates up to RT candidate mask trees; the engine decides
*how*.  The evaluators implement the :class:`CandidateEvaluator` protocol —
``evaluate(stacked_tree) -> (n,) accuracies`` — and are interchangeable from
``run_bcd``'s point of view:

``SequentialEvaluator``
    The reference: one jitted forward per candidate, host loop.  Kept for
    equivalence testing.

``PipelinedEvaluator``
    The full-forward evaluator: one jitted call per chunk of candidates.  On
    one device the program loops over the chunk's real candidates
    (``lax.fori_loop``), one plain forward each, and skips the padded slots;
    under a mesh it vmaps the candidate axis, which the mesh shards
    (``launch.mesh``; on a 2-D ``("cand", "batch")`` mesh the layout is
    chosen per call).  Masks stay jit *inputs* (no recompile across
    chunks); ragged final chunks are padded to the chunk size so the jit
    cache holds one entry per (chunk, shapes) signature.  :meth:`stage`
    pads a chunk, starts its host→device transfer and dispatches its
    program (jax dispatch is async), so the trial loop
    (:func:`evaluate_prefetched`) materializes and stages later chunks while
    the device still computes earlier ones.

``SuffixEvaluator``
    Prefix-reuse (split-forward) evaluation: candidates are local mask
    edits, so for a chunk whose candidates all first differ from the base
    masks at/after one site, everything *before* that site is the same for
    every candidate.  This evaluator computes that shared prefix ONCE per
    (site, step) via the model's ``forward_prefix`` (kept device-resident,
    batch-sharded on a 2-D mesh so it never gathers) and vmaps only
    ``forward_suffix`` over the candidate axis.  Site-aware:
    ``core.bcd._select_block`` feeds it site-grouped chunks
    (:class:`SitedChunk`) in site-major order, and :class:`SuffixCostModel`
    sends shallow-cut chunks down an inner :class:`PipelinedEvaluator`.

Evaluators must rank candidates identically: ``run_bcd`` breaks ties by first
occurrence, and all evaluators score candidates in sampling order, so for a
given seed/config every one selects the same block (tested in
``tests/test_bcd_parallel.py``; the site-aware path reorders *evaluation*
but replays selection in sampling order — ``tests/test_split_forward.py``).
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
from typing import (Any, Callable, Dict, Iterable, Iterator, NamedTuple,
                    Optional, Protocol, Tuple, runtime_checkable)

import numpy as np
import jax
import jax.numpy as jnp

from . import masks as M
from . import tracing


def _donate_mask_arg():
    """``donate_argnums`` for the per-chunk mask stack (argument 0 of a
    chunk program): donating lets XLA reuse the stack's buffers, so a staged
    pipeline stops holding two live copies of every padded chunk.  CPU
    backends don't implement donation and would warn per dispatch, so the
    hint is only emitted where it can be honored."""
    return () if jax.default_backend() == "cpu" else (0,)

# eval_fn: traceable (device mask tree) -> scalar accuracy in percent.
EvalFn = Callable[[dict], jnp.ndarray]


@runtime_checkable
class CandidateEvaluator(Protocol):
    """Evaluates a *stacked* candidate mask tree -> per-candidate accuracy."""

    name: str
    # Chunk size the evaluator wants from run_bcd's trial loop; None defers
    # to cfg.chunk_size.  Chunking never changes selection (rng burns RT
    # draws per step regardless), so this is a pure performance hint.
    preferred_chunk: Optional[int]
    # How many chunks evaluate_prefetched may stage (transfer + dispatch)
    # ahead of the one being consumed.  0 = strict materialize -> evaluate.
    prefetch_depth: int

    def evaluate(self, stacked: M.MaskTree) -> np.ndarray:
        """stacked: {site: (n, *shape)} -> float64 (n,) accuracies [%]."""
        ...


class StagedChunk(NamedTuple):
    """A chunk in flight: transfer + compute dispatched, result not read."""
    n: int                  # true candidate count (before padding)
    accs: jax.Array         # (n_padded,) device array, possibly not ready


def evaluate_prefetched(evaluator, chunks: Iterable[M.MaskTree]
                        ) -> Iterator[np.ndarray]:
    """Producer/consumer driver for the trial loop.

    Yields one float64 ``(n,)`` accuracy array per chunk, in chunk order.
    When the evaluator supports staging (``stage``/``evaluate_staged``, e.g.
    :class:`PipelinedEvaluator`), up to ``prefetch_depth`` chunks beyond the
    one being consumed are kept staged: their host materialization, device
    transfer, and compute dispatch all happen while earlier chunks still
    compute.  Evaluators without staging — or with ``prefetch_depth == 0``
    — degrade to the strict materialize → evaluate alternation.

    The consumer may stop early (ADT exit): closing the generator drops any
    staged-but-unread chunks, and because ``chunks`` is itself pulled lazily,
    chunks beyond the staging horizon are never even materialized.  Chunk k's
    result is always yielded before chunk k+depth+1 is staged, so an early
    exit at chunk k commits at most ``depth`` chunks of wasted work.
    """
    it = iter(chunks)
    depth = int(getattr(evaluator, "prefetch_depth", 0) or 0)
    if depth <= 0 or not hasattr(evaluator, "stage"):
        for chunk in it:
            yield evaluator.evaluate(chunk)
        return
    staged: collections.deque = collections.deque()
    exhausted = False
    while True:
        while not exhausted and len(staged) <= depth:
            try:
                staged.append(evaluator.stage(next(it)))
            except StopIteration:
                exhausted = True
        if not staged:
            return
        yield evaluator.evaluate_staged(staged.popleft())


def _mesh_scope(mesh):
    """Dispatch context for an evaluator's jits: under a mesh, trace and run
    inside ``jax.set_mesh`` so the TPU kernels see it and run per device
    shard (``kernels.ops._per_shard``) — Mosaic kernels cannot be
    partitioned automatically."""
    return contextlib.nullcontext() if mesh is None else jax.set_mesh(mesh)


def _with_stacked_route(eval_fn, *, fused: bool = False):
    """Trace eval_fn under linearize.stacked_kernel_route so the TPU
    hard-mask dispatch emits the custom-vmap routed op: vmapping the
    candidate axis then lowers to the stacked Pallas kernel
    (kernels.masked_act_2d_batched) instead of vmapping the per-candidate
    kernel's grid.  ``fused=True`` additionally arms
    linearize.fused_suffix_route, so models fold the masked-activation gate
    into the adjacent conv/matmul (kernels.ops fused entry points) instead
    of round-tripping the gated tensor through HBM.  Trace-time only — both
    hints are no-ops off TPU."""
    @functools.wraps(eval_fn)
    def routed(*args):
        from . import linearize
        with linearize.stacked_kernel_route():
            if fused:
                with linearize.fused_suffix_route():
                    return eval_fn(*args)
            return eval_fn(*args)
    return routed


def _looped(eval_fn):
    """A chunk program for one device: candidates ``0 .. n-1`` of the mask
    stack, one after another, each a plain forward of ``eval_fn`` (no vmap,
    so every mask site runs the per-candidate gate the step's evaluations
    run).  ``n`` is traced, so one program serves every fill of a padded
    chunk, and slots ``n ..`` are never computed (they read 0).

    A vmap over the chunk would keep every candidate's activations live at
    once, in a candidate-minor layout that costs relayout copies at every
    site: about twice a single forward's memory traffic per candidate."""
    def run(stacked, n, *ctx):
        def body(i, accs):
            masks = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False),
                stacked)
            return accs.at[i].set(eval_fn(masks, *ctx))
        slots = M.stacked_len(stacked)
        return jax.lax.fori_loop(0, n, body, jnp.zeros((slots,), jnp.float32))
    return run


class SequentialEvaluator:
    """Reference backend: unstack and evaluate one candidate at a time."""

    name = "sequential"
    # One candidate per chunk: evaluating a whole chunk before checking the
    # ADT exit would waste up to chunk-1 forwards on this host-loop backend.
    preferred_chunk = 1
    prefetch_depth = 0

    def __init__(self, eval_acc: Callable[[M.MaskTree], float]):
        self._eval_acc = eval_acc

    def evaluate(self, stacked: M.MaskTree) -> np.ndarray:
        n = M.stacked_len(stacked)
        return np.array([float(self._eval_acc(M.index_stacked(stacked, i)))
                         for i in range(n)], dtype=np.float64)


class PipelinedEvaluator:
    """The full-forward evaluator: one jitted call per chunk of candidates,
    staged ahead of the chunk being read.

    The chunk program follows the placement it is given.  Without a mesh it
    loops over the chunk's real candidates (:func:`_looped`).  Under a mesh
    it vmaps the chunk, since the candidate axis is what shards across
    devices:

    - 1-D mesh (``make_candidate_mesh``): every axis shards candidates;
      counts pad up to the device count.
    - 2-D ``("cand", "batch")`` mesh (``make_cand_batch_mesh``): the layout
      is chosen *per call* by padded per-device work
      (:meth:`_chunk_sharding`) — chunks big enough to give every device a
      candidate shard jointly over both axes; smaller chunks shard over
      ``"cand"`` only, and a ``context_specs``-sharded eval batch splits
      each candidate's forward across ``"batch"``.

    ``prefetch_depth`` chunks beyond the one being consumed stay staged:
    padded, transferred, and *dispatched*, so jax's async dispatch overlaps
    chunk k+1's host materialization and H2D transfer with chunk k's device
    compute.  Selection is unchanged: chunks are consumed in sampling order
    and the ADT early exit checks chunk k's results before chunk
    k+1+prefetch_depth is committed.  Every chunk counts
    ``engine.fallback_forwards`` (the forwards the program computes) and
    ``engine.fallback_slots`` (the slots dispatched, padding included).
    """

    name = "pipelined"
    preferred_chunk = None
    prefetch_depth = 2

    def __init__(self, eval_fn: EvalFn, *, pad_to: Optional[int] = None,
                 context=None, mesh=None, context_specs=None):
        """eval_fn: traceable single-tree accuracy (device arrays in/out).
        pad_to: pad ragged candidate axes up to this size (use the BCD
        chunk_size) so jit sees one leading-dim signature.
        context: optional pytree (e.g. model params) passed to eval_fn as a
        second argument and mapped over with in_axes=None.  It is a jit
        *input*, not a closure constant — callers that finetune params
        between outer steps update it via :meth:`set_context` and the
        compiled executable picks up the new values without retracing.
        context_specs: a ``PartitionSpec`` tree for the context
        (:func:`context_batch_specs`); needs a mesh."""
        if context_specs is not None and mesh is None:
            raise ValueError("context_specs requires a mesh")
        if context_specs is not None and context is None:
            raise ValueError("context_specs given without a context")
        self._has_ctx = context is not None
        self._pad_to = pad_to
        self._mesh = mesh
        self._ctx_shardings = None
        if mesh is None:
            program = _looped(eval_fn)
        else:
            from jax.sharding import NamedSharding, PartitionSpec as P
            axes = tuple(mesh.axis_names)
            self._n_dev = int(np.prod([mesh.shape[a] for a in axes]))
            cand_axes = tuple(a for a in axes if a != "batch") or axes
            self._cand = int(np.prod([mesh.shape[a] for a in cand_axes]))
            self._joint_sharding = NamedSharding(mesh, P(axes))
            self._cand_sharding = NamedSharding(mesh, P(cand_axes))
            if context_specs is not None:
                self._ctx_shardings = jax.tree.map(
                    lambda s: NamedSharding(mesh, s), context_specs,
                    is_leaf=lambda x: isinstance(x, P))
            program = jax.vmap(_with_stacked_route(eval_fn),
                               in_axes=(0, None) if self._has_ctx else 0)
        # the mask stack (arg 0) is donated: each staged chunk's stack is a
        # fresh buffer (_device_batch copies) read by exactly one dispatch,
        # so XLA may reuse it in place of a second live copy
        self._program = jax.jit(program, donate_argnums=_donate_mask_arg())
        # commit the context to device once: leaving numpy leaves in the
        # tree makes every dispatch re-transfer them (and re-hash the host
        # arrays), which is pure per-chunk overhead on the hot path
        self.context = None
        if self._has_ctx:
            self.set_context(context)

    def set_context(self, context) -> None:
        """Swap the auxiliary context (same treedef/shapes: no recompile)."""
        if not self._has_ctx:
            raise ValueError("evaluator was built without a context")
        self.context = jax.device_put(context, self._ctx_shardings)

    def _chunk_sharding(self, n: int):
        """Per-call layout under a mesh: (padded candidate count,
        NamedSharding).

        Minimize padded per-device work: the joint layout costs
        ceil(n / n_dev) candidate-forwards per device; the cand-only layout
        costs ceil(n / cand) forwards over 1/batch of the eval batch each.
        Ties prefer joint (no cross-device reduction inside a forward)."""
        batch_ax = self._n_dev // self._cand
        joint_cost = -(-n // self._n_dev)
        split_cost = -(-n // self._cand) / batch_ax
        if joint_cost <= split_cost:
            return n + (-n % self._n_dev), self._joint_sharding
        return n + (-n % self._cand), self._cand_sharding

    def _device_batch(self, stacked: M.MaskTree):
        # np.asarray first: a caller's device array comes to the host and
        # device_put copies it, so the donated stack is always a buffer this
        # evaluator owns.  device_put (not jnp.asarray) so the transfer is an
        # async dispatch the pipeline can run ahead of.
        sharding = None
        if self._mesh is not None:
            n = M.stacked_len(stacked)
            n_pad, sharding = self._chunk_sharding(n)
            if n_pad > n:
                stacked = M.pad_stacked(stacked, n_pad)
        return {k: jax.device_put(np.asarray(v, dtype=np.float32), sharding)
                for k, v in stacked.items()}

    # -------------------------------------------------------------- staging
    #
    # evaluate() is stage() + evaluate_staged(); splitting them lets
    # evaluate_prefetched keep later chunks' transfers AND dispatched
    # computations in flight while it blocks on an earlier chunk's result.

    def stage(self, stacked: M.MaskTree) -> StagedChunk:
        """Pad, start the host→device transfer, dispatch the computation."""
        n = M.stacked_len(stacked)
        if self._pad_to is not None and n < self._pad_to:
            stacked = M.pad_stacked(stacked, self._pad_to)
        batch = self._device_batch(stacked)
        slots = M.stacked_len(batch)
        ctx = (self.context,) if self._has_ctx else ()
        if self._mesh is None:
            forwards = n
            accs = self._program(batch, jax.device_put(np.int32(n)), *ctx)
        else:
            forwards = slots            # the vmap computes every slot
            with _mesh_scope(self._mesh):
                accs = self._program(batch, *ctx)
        tracing.count("engine.fallback_forwards", forwards)
        tracing.count("engine.fallback_slots", slots)
        return StagedChunk(n, accs)

    def evaluate_staged(self, staged: StagedChunk) -> np.ndarray:
        """Block on a staged chunk's result and strip its padding."""
        with tracing.span("engine.wait"):
            return np.asarray(staged.accs, dtype=np.float64)[:staged.n]

    def evaluate(self, stacked: M.MaskTree) -> np.ndarray:
        return self.evaluate_staged(self.stage(stacked))


def effective_chunk(evaluator, chunk_size: int) -> int:
    """The chunk size the trial loop actually uses: evaluators may cap it
    via ``preferred_chunk`` (SequentialEvaluator wants 1 so the ADT exit
    never pays for unevaluated chunk-mates)."""
    return min(chunk_size,
               getattr(evaluator, "preferred_chunk", None) or chunk_size)


def context_batch_specs(context: dict, *, batch_key: str = "batch",
                        axis: str = "batch") -> dict:
    """PartitionSpec tree for an evaluator context dict: leaves under
    ``context[batch_key]`` shard their leading axis over mesh axis ``axis``
    (the axis size must divide their leading dim, e.g. batch 16 over a
    2-device axis); every other leaf replicates.  Feed the result to
    ``PipelinedEvaluator(context_specs=...)`` or ``SuffixEvaluator``."""
    from jax.sharding import PartitionSpec as P
    return {k: jax.tree.map(lambda _: P(axis) if k == batch_key else P(), v)
            for k, v in context.items()}


# ----------------------------------------------------- prefix-reuse backend


@dataclasses.dataclass(frozen=True)
class SuffixCostModel:
    """Per-chunk decision: suffix mode (prefix once + vmapped suffix) vs the
    full-forward :class:`PipelinedEvaluator`, for a chunk of ``n``
    candidates cutting at a site with ``prefix_fraction`` f of forward
    FLOPs above it.

    Per-chunk cost ratio:  suffix / full = ((f - c) + (1 - f)·n) / n, where
    ``c`` is the prefix fraction already resident in the evaluator's trie
    (``covered``) — always <1 for n > 1 even cold, so the analytic *model*
    says "always suffix"; the thresholds price what it can't see: a shallow
    cut's win (f·(n-1) forwards) is smaller than its fixed overheads (one
    extra jit per segment, the cached-acts residency, per-chunk plan/slice
    work), so those sites fall back to the full forward (``use_suffix() ==
    False``).
    """

    min_prefix_fraction: float = 0.05   # below this the reuse is noise
    min_chunk: int = 2                  # n=1 reuses nothing

    def speedup(self, prefix_fraction: float, n: int,
                covered: float = 0.0) -> float:
        """Predicted candidates/sec gain of suffix mode for one chunk;
        ``covered`` discounts prefix work already cached in the trie."""
        f = min(max(prefix_fraction, 0.0), 1.0)
        c = min(max(covered, 0.0), f)
        return n / max((f - c) + (1.0 - f) * n, 1e-9)

    def use_suffix(self, prefix_fraction: float, n: int,
                   covered: float = 0.0) -> bool:
        """The thresholds decide; ``covered`` (what the trie already holds,
        ``SuffixEvaluator.covered_fraction``) can only lower the cost, so a
        cut that clears them cold clears them warm."""
        return n >= self.min_chunk and \
            prefix_fraction >= self.min_prefix_fraction


class SplitEval(NamedTuple):
    """A model's split-forward closure bundle (``make_suffix_eval_fns``).

    ``prefix(site, masks, ctx) -> cached`` and
    ``suffix(site, masks, cached, ctx) -> acc[%]`` satisfy the trace-time
    contract ``suffix(site, m, prefix(site, m, x)) == full(m)`` bitwise for
    every site; ``site`` is Python-level (static) — the evaluator compiles
    one prefix/suffix pair per cut segment.

    ``prefix_ext(from_site, to_site, masks, cached, ctx) -> cached`` extends
    an already-computed prefix by only the segments between the two cuts,
    satisfying ``prefix_ext(a, b, m, prefix(a, m, x)) == prefix(b, m, x)``
    (same fold over the same segment list, so the composition is exact under
    one jit; across jit boundaries the segment outputs are materialized f32
    either way).  Optional: ``None`` disables incremental extension and the
    trie recomputes every prefix from the input.

    ``pre(ctx) -> pre_act`` is the *mask-independent head* of the network
    (input to the first gate's pre-activation — e.g. the stem conv+bn, or
    the LM embed fold).  It depends only on the context, never on candidate
    masks, so the evaluator computes it ONCE per context and ships it inside
    the context as ``ctx["pre"]``; ``full`` then resumes from it, sparing
    every fallback candidate the recompute (``full(m, {**ctx, "pre":
    pre(ctx)}) == full(m, ctx)`` bitwise — the depth-0 analogue of the
    prefix-trie contract).  Optional: ``None`` keeps ``full`` folding from
    the raw input.

    ``site_repeats`` (site -> R) marks mask sites whose (R, ·) array spans
    R consecutive per-repeat cut segments starting at the site's
    ``site_segment`` entry — scanned-stack sites with carry-checkpointed
    per-repeat cuts (models.lm).  ``site_order``/``site_segment`` then also
    carry *virtual* repeat-qualified names (``"s0.ffn@r"`` at segment
    base+r) addressing the per-repeat cuts; ``suffix_sites`` keeps
    returning real mask names only (they key candidate tree slices).
    Grouping resolves each candidate coordinate's repeat row
    arithmetically (``masks.group_blocks_by_site`` ``repeat_sites=``), and
    :meth:`SuffixEvaluator.begin_step` diffs such sites per repeat row so
    trie entries at earlier repeats survive deep-repeat base edits.
    Optional: ``None`` means every site owns exactly one segment.
    """
    prefix: Callable[..., Any]
    suffix: Callable[..., Any]
    full: EvalFn                       # (masks, ctx) -> acc: fallback path
    site_order: Tuple[str, ...]        # topological site order
    site_segment: Dict[str, int]       # site -> cut segment (prefix key)
    suffix_sites: Callable[[str], Tuple[str, ...]]
    prefix_fraction: Dict[str, float]  # site -> fwd-FLOP fraction above it
    prefix_ext: Optional[Callable[..., Any]] = None
    pre: Optional[Callable[..., Any]] = None
    site_repeats: Optional[Dict[str, int]] = None


class SitedChunk(NamedTuple):
    """A candidate chunk annotated with its shared cut site.

    ``site is None`` routes the chunk down the full-forward fallback (the
    cost model declined suffix mode, or the caller had no site info)."""
    site: Optional[str]
    stacked: M.MaskTree


def tree_nbytes(tree) -> int:
    """Total device bytes of a pytree's leaves (global logical bytes for
    sharded arrays — the trie budget is a per-model-replica figure)."""
    return int(sum(np.asarray(leaf).nbytes if not hasattr(leaf, "nbytes")
                   else leaf.nbytes for leaf in jax.tree.leaves(tree)))


class PrefixTrie:
    """Byte-budgeted cache of device-resident prefix activations, keyed by
    cut-segment depth.

    Because every segment has exactly one successor, the "trie" of prefixes
    is a chain: the entry at depth ``d`` is the fold of segments ``[0, d)``
    and is an ancestor of every entry at depth > d.  :meth:`lookup` returns
    the *deepest* cached entry at or above a requested depth, so a chunk
    cutting at ``d`` either hits exactly (reuse), hits an ancestor (extend by
    the segments in between — ``SplitEval.prefix_ext``), or misses (compute
    from the input).

    Eviction is LRU with a site-major (shallow-first) tie-break, bounded by
    ``budget_bytes``: after every insert the total strictly respects the
    budget, evicting least-recently-used entries first and the just-inserted
    entry last (an entry that alone exceeds the budget is dropped too — the
    caller still holds the returned reference for its in-flight dispatches).
    ``budget_bytes=None`` disables eviction.  Counters (``hits`` /
    ``extensions`` / ``misses`` / ``evictions``) are bumped through
    :meth:`_note`, which also counts them as ``engine.prefix_<counter>``
    while ``core.tracing`` records.
    """

    def __init__(self, budget_bytes: Optional[int] = None):
        if budget_bytes is not None and budget_bytes < 0:
            raise ValueError(f"budget_bytes must be >= 0, got {budget_bytes}")
        self.budget_bytes = budget_bytes
        self._entries: Dict[int, Any] = {}
        self._nbytes: Dict[int, int] = {}
        self._tick: Dict[int, int] = {}
        self._clock = 0
        self.hits = 0
        self.extensions = 0
        self.misses = 0
        self.evictions = 0

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, depth: int) -> bool:
        return depth in self._entries

    def depths(self) -> Tuple[int, ...]:
        return tuple(sorted(self._entries))

    def items(self):
        return self._entries.items()

    def total_bytes(self) -> int:
        return sum(self._nbytes.values())

    def lookup(self, depth: int) -> Optional[Tuple[int, Any]]:
        """Deepest cached ancestor at depth <= ``depth`` -> (depth, cached),
        or None.  Touches the entry's LRU tick."""
        live = [d for d in self._entries if d <= depth]
        if not live:
            return None
        d = max(live)
        self._clock += 1
        self._tick[d] = self._clock
        return d, self._entries[d]

    def insert(self, depth: int, cached, nbytes: Optional[int] = None) -> None:
        self._entries[depth] = cached
        self._nbytes[depth] = tree_nbytes(cached) if nbytes is None else nbytes
        self._clock += 1
        self._tick[depth] = self._clock
        self._evict(newest=depth)

    def keep_where(self, pred: Callable[[int], bool]) -> None:
        """Drop every entry whose depth fails ``pred`` (cross-step
        invalidation: keep depths unaffected by changed base masks)."""
        for d in [d for d in self._entries if not pred(d)]:
            self._drop(d)

    def clear(self) -> None:
        self._entries.clear()
        self._nbytes.clear()
        self._tick.clear()

    def _note(self, kind: str) -> None:
        setattr(self, kind, getattr(self, kind) + 1)
        tracing.count("engine.prefix_" + kind)

    def _drop(self, depth: int) -> None:
        del self._entries[depth]
        del self._nbytes[depth]
        del self._tick[depth]

    def _evict(self, newest: int) -> None:
        if self.budget_bytes is None:
            return
        while self.total_bytes() > self.budget_bytes:
            victims = sorted((d for d in self._entries if d != newest),
                             key=lambda d: (self._tick[d], d))
            victim = victims[0] if victims else newest
            self._drop(victim)
            self._note("evictions")
            if victim == newest:
                break


class SuffixEvaluator:
    """Prefix-reuse backend: one shared prefix per (site, step), vmapped
    suffix per candidate.

    The trial loop (``core.bcd._select_block``) calls :meth:`begin_step`
    with the step's base masks, then feeds :class:`SitedChunk`\\ s grouped
    site-major (``plan_sited_chunks``).  For each chunk the cut segment's
    prefix comes from a :class:`PrefixTrie` of device-resident activations:
    an exact-depth hit is reused outright; otherwise the deepest cached
    *ancestor* is extended by only the segments between its depth and the
    cut (``SplitEval.prefix_ext``), so consuming chunks shallow-to-deep
    turns the step's prefix work into one incremental pass over the network
    instead of one full prefix per segment.  Candidates never mutate sites
    above their cut, so prefixes depend only on the step's *base* masks —
    which also lets entries survive across outer steps: :meth:`begin_step`
    diffs the new base tree against the old one and keeps every entry whose
    depth is at or above no changed site (selective invalidation).  Entries
    stay batch-sharded on a 2-D ``("cand", "batch")`` mesh — lookup,
    extension, and eviction never gather them.  Residency is bounded by
    ``trie_budget_bytes`` (LRU, site-major tie-break).  Suffix dispatches
    ship only the *suffix-site* mask slices (sharded over ``"cand"``), so
    deep-site chunks also transfer a fraction of the mask bytes.

    Plain (un-sited) chunks and cost-model fallbacks delegate to an inner
    :class:`PipelinedEvaluator` sharing the same context/placement; sited
    chunks stage the same way (stage = slice + pad + transfer + dispatch
    suffix), at the inner evaluator's ``prefetch_depth``.  The fallback
    evaluator is built once and kept warm: consecutive fallback chunks
    reuse its jit executable and its device-committed context — no
    per-chunk re-staging cost.  When the model provides ``SplitEval.pre``
    (the mask-independent head fold — stem conv+bn / embed), it is computed
    once per context and shipped as ``ctx["pre"]``, so even fallback
    candidates skip the head recompute: the depth-0 analogue of the prefix
    trie.

    ``fused_kernels`` traces the suffix jits under
    ``linearize.fused_suffix_route`` so TPU hard-mask sites fuse the gate
    into the adjacent conv/matmul (kernels.ops fused entry points); inert
    off-TPU, where dispatch falls through to the reference path.
    """

    name = "suffix"
    site_aware = True
    preferred_chunk = None
    prefetch_depth = PipelinedEvaluator.prefetch_depth

    def __init__(self, split: SplitEval, *, pad_to: Optional[int] = None,
                 context=None, mesh=None, context_specs=None,
                 cost_model: Optional[SuffixCostModel] = None,
                 trie_budget_bytes: Optional[int] = None,
                 fused_kernels: bool = True):
        if not isinstance(context, dict) or "params" not in context \
                or "batch" not in context:
            raise ValueError(
                "SuffixEvaluator needs context={'params': …, 'batch': …} — "
                "prefix and suffix consume the eval batch and params as jit "
                "inputs (models' make_suffix_eval_fns contract)")
        self._split = split
        self.cost_model = cost_model or SuffixCostModel()
        self.fused_kernels = bool(fused_kernels)
        self._pad_to = pad_to
        self._mesh = mesh
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            self._cache_sharding = NamedSharding(
                mesh, P("batch") if "batch" in mesh.axis_names else P())
        # mask-independent head fold (SplitEval.pre): computed once per
        # context and shipped INSIDE the inner context, so every fallback
        # full-forward resumes from it instead of re-tracing the stem/embed
        self._pre_jit = None if split.pre is None else jax.jit(split.pre)
        context = self._with_pre(context)
        if context_specs is not None and "pre" in context:
            from jax.sharding import PartitionSpec as P
            axes = tuple(mesh.axis_names) if mesh is not None else ()
            spec = P("batch") if "batch" in axes else P()
            context_specs = {**context_specs,
                             "pre": jax.tree.map(lambda _: spec,
                                                 context["pre"])}
        self._inner = PipelinedEvaluator(
            split.full, pad_to=pad_to, context=context, mesh=mesh,
            context_specs=context_specs)
        # one representative site per segment: sites cutting at the same
        # segment share the prefix cache entry and the prefix/suffix jits
        self._segment_site: Dict[int, str] = {}
        for s in split.site_order:
            self._segment_site.setdefault(split.site_segment[s], s)
        self._prefix_jits: Dict[int, Callable] = {}
        self._prefix_ext_jits: Dict[Tuple[int, int], Callable] = {}
        self._suffix_jits: Dict[int, Callable] = {}
        self.trie = PrefixTrie(budget_bytes=trie_budget_bytes)
        self._base_masks: Optional[M.MaskTree] = None
        self._base_dev: Optional[dict] = None   # device copy, lazy per step

    def _with_pre(self, context):
        """Augment a raw context with the mask-independent head fold
        (``ctx["pre"]``), batch-sharded under a mesh like the trie cache —
        suffix/prefix closures ignore the extra key; ``split.full`` resumes
        from it."""
        if self._pre_jit is None:
            return context
        with _mesh_scope(self._mesh):
            pre = self._pre_jit(context)
        if self._mesh is not None:
            pre = jax.device_put(pre, self._cache_sharding)
        return {**context, "pre": pre}

    # context lives on the inner evaluator (single source of truth; it owns
    # the device placement / context_specs resharding)
    @property
    def context(self):
        return self._inner.context

    def set_context(self, context) -> None:
        """Swap params/batch context; cached prefixes are invalidated (they
        were computed from the old params/batch) and the mask-independent
        head fold is recomputed from the new context."""
        self._inner.set_context(self._with_pre(context))
        self.trie.clear()

    def begin_step(self, base_masks: M.MaskTree) -> None:
        """Fix the outer step's base mask tree (what prefixes are computed
        from) and selectively invalidate the trie.  The trial loop calls
        this once per step, before any sited chunk is staged.

        A trie entry at depth ``d`` folds segments ``[0, d)``, so it reads
        exactly the base masks of sites with segment < d: diffing the new
        base tree against the previous step's, entries with
        ``d <= min(changed segments)`` are still byte-identical prefixes and
        survive.  A BCD step that only flipped coordinates at/below the
        deepest cut (the common case late in a sweep) therefore keeps its
        whole chain warm.

        Sites in ``SplitEval.site_repeats`` (scanned-stack masks spanning R
        per-repeat segments) are diffed per repeat ROW: the effective
        changed segment is the site's base segment plus the first repeat
        row that differs, so a base edit at repeat r keeps every carry
        checkpoint at repeats <= r warm instead of flushing the whole
        stack's chain."""
        new = {k: np.asarray(v, dtype=np.float32)
               for k, v in base_masks.items()}
        if self._base_masks is None or set(new) != set(self._base_masks):
            self.trie.clear()
        elif len(self.trie):
            reps = self._split.site_repeats or {}
            changed = []
            for k in new:
                if np.array_equal(new[k], self._base_masks[k]):
                    continue
                seg = self._split.site_segment[k]
                rk = int(reps.get(k, 1))
                if rk > 1:
                    rows = np.any(new[k].reshape(rk, -1)
                                  != self._base_masks[k].reshape(rk, -1),
                                  axis=1)
                    seg += int(np.flatnonzero(rows)[0])
                changed.append(seg)
            if changed:
                min_seg = min(changed)
                self.trie.keep_where(lambda d: d <= min_seg)
        self._base_masks = new
        self._base_dev = None

    def prefix_fraction(self, site: str) -> float:
        return self._split.prefix_fraction[site]

    # ----------------------------------------------------------- internals

    def _base_masks_dev(self) -> dict:
        if self._base_masks is None:
            raise RuntimeError(
                "SuffixEvaluator.begin_step(base_masks) must be called "
                "before sited evaluation (the prefix needs the step's base "
                "mask tree)")
        if self._base_dev is None:
            self._base_dev = {k: jnp.asarray(v)
                              for k, v in self._base_masks.items()}
        return self._base_dev

    def covered_fraction(self, site: str) -> float:
        """Prefix-FLOP fraction already resident in the trie for a cut at
        ``site``'s segment — the planner prices suffix mode with only the
        *incremental* prefix cost (cut fraction minus this)."""
        seg = self._split.site_segment[site]
        live = [d for d in self.trie.depths() if d <= seg]
        if not live:
            return 0.0
        anc_site = self._segment_site.get(max(live))
        if anc_site is None:
            return 0.0
        return self._split.prefix_fraction[anc_site]

    def _pin(self, cached):
        if self._mesh is not None:
            # pin the cache batch-sharded: suffix dispatches read it in
            # place (in_axes=None) — it is never gathered across "batch"
            return jax.device_put(cached, self._cache_sharding)
        return cached

    def _prefix_for(self, site: str):
        seg = self._split.site_segment[site]
        hit = self.trie.lookup(seg)
        if hit is not None and hit[0] == seg:
            self.trie._note("hits")
            return hit[1]
        with tracing.span("engine.prefix"):
            base = self._base_masks_dev()
            if hit is not None and self._split.prefix_ext is not None:
                # deepest-ancestor extension: fold only segments
                # [hit_depth, seg)
                from_seg, ancestor = hit
                key = (from_seg, seg)
                jit_fn = self._prefix_ext_jits.get(key)
                if jit_fn is None:
                    jit_fn = jax.jit(functools.partial(
                        self._split.prefix_ext, self._segment_site[from_seg],
                        self._segment_site[seg]))
                    self._prefix_ext_jits[key] = jit_fn
                cached = self._pin(jit_fn(base, ancestor, self.context))
                self.trie._note("extensions")
            else:
                jit_fn = self._prefix_jits.get(seg)
                if jit_fn is None:
                    jit_fn = jax.jit(functools.partial(
                        self._split.prefix, self._segment_site[seg]))
                    self._prefix_jits[seg] = jit_fn
                cached = self._pin(jit_fn(base, self.context))
                self.trie._note("misses")
            self.trie.insert(seg, cached)
        return cached

    def _suffix_for(self, site: str):
        seg = self._split.site_segment[site]
        jit_fn = self._suffix_jits.get(seg)
        if jit_fn is None:
            routed = _with_stacked_route(
                functools.partial(self._split.suffix,
                                  self._segment_site[seg]),
                fused=self.fused_kernels)
            # masks stack donated, prefix cache and context read-only
            jit_fn = jax.jit(jax.vmap(routed, in_axes=(0, None, None)),
                             donate_argnums=_donate_mask_arg())
            self._suffix_jits[seg] = jit_fn
        return jit_fn

    def _stage_sited(self, site: str, stacked: M.MaskTree) -> StagedChunk:
        with tracing.span("engine.stage"):
            n = M.stacked_len(stacked)
            # ship only the masks the suffix consumes (sites at/after the cut)
            sub = {k: stacked[k] for k in self._split.suffix_sites(site)}
            n_pad = max(n, self._pad_to or 0)
            sharding = None
            if self._mesh is not None:
                # candidates shard over "cand" as the fallback's do
                n_pad += -n_pad % self._inner._cand
                sharding = self._inner._cand_sharding
            if n_pad > n:
                sub = M.pad_stacked(sub, n_pad)
            batch = {k: jax.device_put(np.asarray(v, dtype=np.float32),
                                       sharding)
                     for k, v in sub.items()}
            with _mesh_scope(self._mesh):
                cached = self._prefix_for(site)
                accs = self._suffix_for(site)(batch, cached, self.context)
            return StagedChunk(n, accs)

    # ------------------------------------------------------------- protocol

    def stage(self, item) -> StagedChunk:
        """Stage a chunk: ``SitedChunk`` with a site takes the suffix path;
        everything else (plain stacked trees, cost-model fallbacks) stages
        on the inner full-forward pipeline."""
        if isinstance(item, SitedChunk):
            if item.site is None:
                return self._inner.stage(item.stacked)
            return self._stage_sited(item.site, item.stacked)
        return self._inner.stage(item)

    def evaluate_staged(self, staged: StagedChunk) -> np.ndarray:
        return self._inner.evaluate_staged(staged)

    def evaluate(self, item) -> np.ndarray:
        return self.evaluate_staged(self.stage(item))


def plan_sited_chunks(evaluator: SuffixEvaluator, indices, layout: list,
                      chunk_size: int):
    """Site-major evaluation plan for the suffix backend.

    ``indices`` is either an (n, k) flat-coordinate array
    (``masks.sample_removal_indices``) or a list of typed
    :class:`masks.Move` candidates (``masks.sample_moves``).

    Returns ``(order, chunks)``: ``order`` is a permutation of candidate
    positions — grouped by the *cut segment* of each candidate's earliest
    touched site, sampling order preserved within a group — and ``chunks``
    is ``[(site | None, start, stop)]`` bounds into ``order``.  Sited
    chunks never straddle a group, so every sited chunk shares one prefix;
    groups are emitted depth-ascending, so the trie extends each prefix
    from its predecessor instead of recomputing from the input (the trie
    locality ``core.bcd._scan_sited`` relies on).  Multi-site moves (swap /
    share / add_back) group by the *shallowest* site they touch — over
    off ∪ on ∪ tie (``masks.group_moves_by_site``) — because a cached
    prefix is only reusable if it reads none of the candidate's edited
    masks.  Scanned-stack sites with per-repeat cuts
    (``SplitEval.site_repeats``) resolve each coordinate to its repeat
    row's segment, so a candidate editing only repeat r cuts at r's carry
    checkpoint instead of the whole stack's entry.
    ``site is None`` marks chunks the cost model sent down the
    full-forward fallback (shallow cut or undersized chunk); runs of
    adjacent fallback chunks are coalesced back up to ``chunk_size``
    (``masks.coalesce_fallback_chunks``) so a fragmented depth mix doesn't
    degrade the inner pipeline into ragged dispatches.

    Suffix-vs-fallback pricing is trie-aware: the cost model sees the cut's
    prefix fraction *and* the fraction already resident in the trie
    (``SuffixEvaluator.covered_fraction``), so a warm trie makes suffix
    mode cheaper than the analytic cold-start estimate.  The plan must be
    built after :meth:`SuffixEvaluator.begin_step` — surviving entries are
    part of the price."""
    split = evaluator._split
    if isinstance(indices, (list, tuple)):
        order, groups = M.group_moves_by_site(indices, layout,
                                              split.site_segment,
                                              repeat_sites=split.site_repeats)
    else:
        order, groups = M.group_blocks_by_site(
            indices, layout, split.site_segment,
            repeat_sites=split.site_repeats)
    raw = []
    planned_cover = 0.0   # prefixes earlier planned chunks will have cached
    for seg, g0, g1 in groups:
        site = evaluator._segment_site.get(seg)
        frac = split.prefix_fraction[site] if site is not None else 0.0
        covered = 0.0
        if site is not None:
            covered = min(max(evaluator.covered_fraction(site),
                              planned_cover), frac)
        group_sited = False
        for s, e in M.chunk_bounds(g1 - g0, chunk_size):
            n = e - s
            use = site is not None and \
                evaluator.cost_model.use_suffix(frac, n, covered)
            group_sited = group_sited or use
            raw.append((site if use else None, g0 + s, g0 + e))
        if group_sited:
            planned_cover = max(planned_cover, frac)
    return order, M.coalesce_fallback_chunks(raw, chunk_size)


def materialize_sited(flat: np.ndarray, layout: list, indices,
                      order: np.ndarray, chunks) -> Iterator[SitedChunk]:
    """Lazy :class:`SitedChunk` producer over a ``plan_sited_chunks`` plan
    (the site-aware counterpart of ``masks.materialize_chunks`` — same
    laziness contract: the prefetch pipeline pulls it, early exit closes
    it).  ``indices`` matches ``plan_sited_chunks``: an (n, k) removal
    array or a list of typed ``masks.Move`` candidates."""
    typed = isinstance(indices, (list, tuple))
    for site, s, e in chunks:
        sel = order[s:e]
        if typed:
            stacked = M.materialize_moves_from_flat(
                flat, layout, [indices[int(i)] for i in sel])
        else:
            stacked = M.materialize_from_flat(flat, layout, indices[sel])
        yield SitedChunk(site, stacked)
