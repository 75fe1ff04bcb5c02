"""Per-kernel correctness: Pallas (interpret mode) vs pure-jnp oracles,
swept over shapes / dtypes / activation kinds, plus hypothesis properties."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

# hypothesis is an optional dev dep (pip extra: test) — bare environments
# must still collect/run the deterministic kernel tests, so only the
# property tests below are guarded.
try:
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ImportError:
    HAS_HYPOTHESIS = False

from repro.kernels import ops, ref
from repro.kernels.masked_act import masked_act_2d, masked_act_2d_batched
from repro.kernels.rwkv6_scan import rwkv6_scan

KINDS = ["relu", "gelu", "silu", "sqrelu"]


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("shape", [(8, 128), (37, 200), (128, 512), (3, 64)])
@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_masked_act_matches_oracle(kind, shape, dtype):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=shape).astype(dtype))
    m = jnp.asarray((rng.random(shape[1]) > 0.5).astype(np.float32))
    want = ref.masked_act_ref(x, m, kind=kind)
    got = masked_act_2d(x, m, kind=kind, interpret=True,
                        block_rows=16, block_cols=128)
    tol = 1e-5 if dtype == np.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("kind", ["relu", "gelu"])
def test_masked_act_poly_matches_oracle(kind):
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(33, 130)).astype(np.float32))
    m = jnp.asarray((rng.random(130) > 0.3).astype(np.float32))
    poly = jnp.asarray(rng.normal(size=(3, 130)).astype(np.float32) * 0.1)
    want = ref.masked_act_ref(x, m, kind=kind, poly=poly)
    got = masked_act_2d(x, m, poly, kind=kind, interpret=True,
                        block_rows=8, block_cols=128)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.fixture
def tpu_dispatch(monkeypatch):
    """The TPU's kernel dispatch on the CPU: ``ops._use_pallas`` forced
    true and every ``pallas_call`` in interpret mode."""
    from jax.experimental import pallas as pl
    call = pl.pallas_call
    monkeypatch.setattr(pl, "pallas_call",
                        lambda *a, **k: call(*a, **{**k, "interpret": True}))
    monkeypatch.setattr(ops, "_use_pallas", lambda: True)


def _gate_inputs(seed, site_shape, batch=2):
    """Activations, a hard mask holding off, on and share-tied
    (``masks.TIE``) coordinates, and a cotangent."""
    from repro.core import masks as M
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(batch,) + site_shape)
                    .astype(np.float32))
    m = jnp.asarray(rng.choice(np.float32([0.0, 1.0, M.TIE]),
                               size=site_shape))
    cot = jnp.asarray(rng.normal(size=x.shape).astype(np.float32))
    return x, m, cot


def _oracle_gate(x, m, kind, poly=None):
    """The hard-mask gate by its definition: the kernels' oracle, then the
    share-ties override (``linearize._apply_share_ties``)."""
    from repro.core import linearize
    return linearize._apply_share_ties(
        x, m, ref.masked_act_ref(x, m, kind=kind, poly=poly))


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("with_poly", [False, True])
def test_masked_act_kernel_grad_matches_oracle_grad(kind, with_poly,
                                                    tpu_dispatch):
    """The TPU's hard-mask gate differentiates (training, SNL/BCD finetune
    and the LM train step run it): its gradients with respect to x, mask
    and poly are the oracle's."""
    from repro.core import linearize
    x, m, cot = _gate_inputs(2, (9, 130))
    rng = np.random.default_rng(3)
    poly = (jnp.asarray(rng.normal(size=(3, 9, 130)).astype(np.float32)
                        * 0.1) if with_poly else None)
    site = linearize.MaskSite((9, 130), kind,
                              "poly2" if with_poly else "identity")

    def loss(f):
        return lambda x, m, p: jnp.sum(f(x, m, p) * cot)

    gate = loss(lambda x, m, p: linearize.apply_masked_act(x, m, site,
                                                           poly=p))
    oracle = loss(lambda x, m, p: _oracle_gate(x, m, kind, poly=p))
    argnums = (0, 1, 2) if with_poly else (0, 1)
    got = jax.jit(jax.grad(gate, argnums))(x, m, poly)
    want = jax.jit(jax.grad(oracle, argnums))(x, m, poly)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("kind", KINDS)
def test_unrouted_hard_gate_is_xlas_on_the_tpu_dispatch(kind, tpu_dispatch):
    """Outside any route, the TPU's hard-mask gate is the plain broadcast
    expression: its jaxpr holds no ``pallas_call`` (XLA fuses the gate into
    the op that produces x), and it computes the oracle's gate with the
    share-ties override."""
    from repro.core import linearize
    x, m, _ = _gate_inputs(12, (4, 4, 8))
    site = linearize.MaskSite((4, 4, 8), kind)
    gate = lambda x, m: linearize.apply_masked_act(x, m, site)
    assert "pallas_call" not in str(jax.make_jaxpr(gate)(x, m))
    np.testing.assert_allclose(gate(x, m), _oracle_gate(x, m, kind),
                               rtol=1e-6, atol=1e-6)


def test_stacked_route_still_lowers_to_the_stacked_kernel(tpu_dispatch,
                                                          monkeypatch):
    """Under ``stacked_kernel_route`` the TPU's hard gate keeps its Pallas
    kernels: a candidate vmap traces the stacked kernel
    (``masked_act_2d_batched``), an unbatched call the per-candidate one,
    and both agree with the oracle's gate per candidate."""
    from repro.core import linearize
    stacked = []
    batched = ops.masked_act_2d_batched
    monkeypatch.setattr(ops, "masked_act_2d_batched",
                        lambda *a, **k: stacked.append(1) or batched(*a, **k))
    x, _, _ = _gate_inputs(13, (4, 4, 8))
    masks = jnp.stack([_gate_inputs(14 + i, (4, 4, 8))[1] for i in range(3)])
    site = linearize.MaskSite((4, 4, 8), "relu")
    gate = lambda m: linearize.apply_masked_act(x, m, site)
    with linearize.stacked_kernel_route():
        jaxpr = str(jax.make_jaxpr(jax.vmap(gate))(masks))
        got = jax.vmap(gate)(masks)
        one = str(jax.make_jaxpr(gate)(masks[0]))
    assert stacked and "pallas_call" in jaxpr and "pallas_call" in one
    for i in range(3):
        np.testing.assert_allclose(got[i], _oracle_gate(x, masks[i],
                                                            "relu"),
                                   rtol=1e-6, atol=1e-6)


if HAS_HYPOTHESIS:
    @given(rows=st.integers(1, 64), cols=st.integers(1, 300),
           frac=st.floats(0, 1), seed=st.integers(0, 2**31 - 1))
    @settings(max_examples=20, deadline=None)
    def test_masked_act_mask_semantics(rows, cols, frac, seed):
        """mask==1 ⇒ act(x); mask==0 ⇒ x (identity replacement) — exactly."""
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.normal(size=(rows, cols)).astype(np.float32))
        m = jnp.asarray((rng.random(cols) < frac).astype(np.float32))
        y = np.asarray(ref.masked_act_ref(x, m, kind="relu"))
        xn = np.asarray(x)
        keep = np.asarray(m) > 0.5
        np.testing.assert_allclose(y[:, keep], np.maximum(xn[:, keep], 0))
        np.testing.assert_allclose(y[:, ~keep], xn[:, ~keep])
else:
    def test_masked_act_mask_semantics():
        pytest.skip("hypothesis not installed (pip extra: test)")


@pytest.mark.parametrize("kind", ["relu", "gelu"])
@pytest.mark.parametrize("n", [1, 3, 8])
def test_masked_act_batched_matches_per_candidate(kind, n):
    """The stacked-candidate kernel == n independent 2D kernel calls."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(n, 37, 200)).astype(np.float32))
    m = jnp.asarray((rng.random((n, 200)) > 0.5).astype(np.float32))
    got = masked_act_2d_batched(x, m, kind=kind, interpret=True,
                                block_rows=16, block_cols=128)
    for i in range(n):
        want = masked_act_2d(x[i], m[i], kind=kind, interpret=True,
                             block_rows=16, block_cols=128)
        np.testing.assert_allclose(got[i], want, rtol=1e-6, atol=1e-6)


def test_masked_act_batched_poly_shared_across_candidates():
    rng = np.random.default_rng(4)
    n, rows, cols = 4, 16, 130
    x = jnp.asarray(rng.normal(size=(n, rows, cols)).astype(np.float32))
    m = jnp.asarray((rng.random((n, cols)) > 0.4).astype(np.float32))
    poly = jnp.asarray(rng.normal(size=(3, cols)).astype(np.float32) * 0.1)
    got = masked_act_2d_batched(x, m, poly, kind="relu", interpret=True,
                                block_rows=8, block_cols=128)
    for i in range(n):
        want = ref.masked_act_ref(x[i], m[i], kind="relu", poly=poly)
        np.testing.assert_allclose(got[i], want, rtol=1e-5, atol=1e-5)


def test_ops_masked_act_batched_dispatch_matches_kernel():
    """CPU ref fallback of ops.masked_act_batched == interpret-mode kernel."""
    from repro.kernels.ops import masked_act_batched
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(3, 4, 10, 64)).astype(np.float32))
    m = jnp.asarray((rng.random((3, 64)) > 0.5).astype(np.float32))
    via_ref = masked_act_batched(x, m, kind="silu")
    via_kernel = masked_act_batched(x, m, kind="silu", force_pallas=True,
                                    interpret=True)
    np.testing.assert_allclose(via_ref, via_kernel, rtol=1e-5, atol=1e-5)


def test_ops_masked_act_sited_batched_matches_per_candidate_sited():
    """Stacked site masks (N, *site) == N independent masked_act_sited
    calls, on both dispatch paths (CNN-style (H, W, C) site)."""
    from repro.kernels.ops import masked_act_sited, masked_act_sited_batched
    rng = np.random.default_rng(6)
    n, B, site = 3, 2, (4, 4, 8)
    x = jnp.asarray(rng.normal(size=(n, B) + site).astype(np.float32))
    m = jnp.asarray((rng.random((n,) + site) > 0.5).astype(np.float32))
    poly = jnp.asarray(rng.normal(size=(3,) + site).astype(np.float32) * 0.1)
    for kw in ({}, {"force_pallas": True, "interpret": True}):
        got = masked_act_sited_batched(x, m, kind="relu", poly=poly, **kw)
        assert got.shape == x.shape
        for i in range(n):
            want = masked_act_sited(x[i], m[i], kind="relu", poly=poly)
            np.testing.assert_allclose(got[i], want, rtol=1e-5, atol=1e-5)


def test_masked_act_sited_routed_vmaps_to_stacked_kernel():
    """The custom-vmap entry (the BCD engines' TPU route): vmapping the
    candidate axis must produce exactly what N per-candidate sited calls
    produce — for batched x, unbatched x (mask-independent activations),
    and the poly replacement; unbatched calls fall through to the base."""
    from repro.kernels.ops import masked_act_sited, masked_act_sited_routed
    rng = np.random.default_rng(7)
    n, B, site = 4, 2, (4, 4, 8)
    x = jnp.asarray(rng.normal(size=(n, B) + site).astype(np.float32))
    m = jnp.asarray((rng.random((n,) + site) > 0.5).astype(np.float32))
    poly = jnp.asarray(rng.normal(size=(3,) + site).astype(np.float32) * 0.1)

    # both batched
    got = jax.vmap(lambda xi, mi: masked_act_sited_routed(
        xi, mi, kind="relu", interpret=True))(x, m)
    w = jnp.stack([masked_act_sited(x[i], m[i], kind="relu",
                                    force_pallas=True, interpret=True)
                   for i in range(n)])
    np.testing.assert_allclose(np.asarray(got), np.asarray(w),
                               rtol=1e-6, atol=1e-6)

    # mask-only batched (x shared across candidates — the first mask site)
    x1 = x[0]
    got = jax.vmap(lambda mi: masked_act_sited_routed(
        x1, mi, kind="gelu", interpret=True))(m)
    w = jnp.stack([masked_act_sited(x1, m[i], kind="gelu",
                                    force_pallas=True, interpret=True)
                   for i in range(n)])
    np.testing.assert_allclose(np.asarray(got), np.asarray(w),
                               rtol=1e-5, atol=1e-5)

    # poly replacement, shared across candidates; under jit like the engine
    got = jax.jit(jax.vmap(lambda xi, mi: masked_act_sited_routed(
        xi, mi, kind="relu", poly=poly, interpret=True)))(x, m)
    w = jnp.stack([masked_act_sited(x[i], m[i], kind="relu", poly=poly,
                                    force_pallas=True, interpret=True)
                   for i in range(n)])
    np.testing.assert_allclose(np.asarray(got), np.asarray(w),
                               rtol=1e-5, atol=1e-5)

    # no vmap: falls through to the per-candidate kernel
    got = masked_act_sited_routed(x[0], m[0], kind="relu", interpret=True)
    w = masked_act_sited(x[0], m[0], kind="relu", force_pallas=True,
                         interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(w),
                               rtol=1e-6, atol=1e-6)


def test_suffix_route_unbatched_cache_batched_masks():
    """The prefix-reuse engine's layout: a vmapped suffix forward maps
    masks over the candidate axis while the cached prefix activation rides
    in_axes=None (shared across candidates) and feeds further ops.  The
    custom-vmap rule must broadcast x across the candidate axis into the
    stacked kernel and agree with the per-candidate reference."""
    from repro.kernels.ops import masked_act_sited, masked_act_sited_routed
    rng = np.random.default_rng(11)
    n, B, site_shape = 3, 2, (4, 4, 8)
    cached = jnp.asarray(rng.normal(size=(B,) + site_shape)
                         .astype(np.float32))
    masks = jnp.asarray((rng.random((n,) + site_shape) > 0.5)
                        .astype(np.float32))

    def suffix_fn(m, x):
        y = masked_act_sited_routed(x, m, kind="relu", interpret=True)
        return y.reshape(B, -1).sum(-1)          # downstream suffix ops

    got = jax.jit(jax.vmap(suffix_fn, in_axes=(0, None)))(masks, cached)
    want = jnp.stack([
        masked_act_sited(cached, masks[i], kind="relu", force_pallas=True,
                         interpret=True).reshape(B, -1).sum(-1)
        for i in range(n)])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_stacked_kernel_route_hint_is_scoped():
    """linearize.stacked_kernel_route flips the trace-time flag and always
    restores it (exceptions included)."""
    from repro.core import linearize
    assert not linearize.stacked_route_active()
    with linearize.stacked_kernel_route():
        assert linearize.stacked_route_active()
        with linearize.stacked_kernel_route(False):
            assert not linearize.stacked_route_active()
        assert linearize.stacked_route_active()
    assert not linearize.stacked_route_active()
    with pytest.raises(RuntimeError):
        with linearize.stacked_kernel_route():
            raise RuntimeError("boom")
    assert not linearize.stacked_route_active()


def test_full_mask_is_pure_activation_and_zero_mask_is_identity():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(16, 128)).astype(np.float32))
    ones = jnp.ones((128,))
    zeros = jnp.zeros((128,))
    got = masked_act_2d(x, ones, kind="silu", interpret=True)
    np.testing.assert_allclose(got, jax.nn.silu(x), rtol=1e-6, atol=1e-6)
    got = masked_act_2d(x, zeros, kind="silu", interpret=True)
    np.testing.assert_allclose(got, x, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("T,K,V,chunk", [(32, 8, 8, 8), (64, 16, 32, 16),
                                         (64, 8, 16, 32)])
def test_rwkv6_pallas_vs_scan(T, K, V, chunk):
    rng = np.random.default_rng(3)
    BH = 4
    r = jnp.asarray(rng.normal(size=(BH, T, K)).astype(np.float32)) * 0.5
    k = jnp.asarray(rng.normal(size=(BH, T, K)).astype(np.float32)) * 0.5
    v = jnp.asarray(rng.normal(size=(BH, T, V)).astype(np.float32))
    w = jnp.asarray(rng.uniform(0.7, 0.999, size=(BH, T, K)).astype(np.float32))
    u = jnp.asarray(rng.normal(size=(BH, K)).astype(np.float32)) * 0.3
    s0 = jnp.asarray(rng.normal(size=(BH, K, V)).astype(np.float32)) * 0.1
    y_ref, s_ref = ops._rwkv6_scan_jnp(r, k, v, w, u, s0)
    y_pl, s_pl = rwkv6_scan(r, k, v, w, u, s0, chunk=chunk, interpret=True)
    np.testing.assert_allclose(y_pl, y_ref, rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(s_pl, s_ref, rtol=3e-4, atol=3e-4)


def test_rwkv6_scan_oracle_vs_python_loop():
    rng = np.random.default_rng(4)
    T, K, V = 24, 4, 8
    r, k = (jnp.asarray(rng.normal(size=(1, T, K)).astype(np.float32))
            for _ in range(2))
    v = jnp.asarray(rng.normal(size=(1, T, V)).astype(np.float32))
    w = jnp.asarray(rng.uniform(0.8, 1, size=(1, T, K)).astype(np.float32))
    u = jnp.asarray(rng.normal(size=(1, K)).astype(np.float32))
    s0 = jnp.zeros((1, K, V))
    y1, s1 = ref.rwkv6_chunk_ref(r[0], k[0], v[0], w[0], u[0], s0[0])
    y2, s2 = ops._rwkv6_scan_jnp(r, k, v, w, u, s0)
    np.testing.assert_allclose(y2[0], y1, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(s2[0], s1, rtol=2e-5, atol=2e-5)


def test_ops_dispatch_cpu_uses_ref():
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(4, 8, 16)).astype(np.float32))
    m = jnp.asarray(np.ones(16, np.float32))
    out = ops.masked_act(x, m, kind="gelu")
    np.testing.assert_allclose(out, ref.masked_act_ref(x, m, kind="gelu"))
