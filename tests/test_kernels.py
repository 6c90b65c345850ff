"""Per-kernel validation: shape/dtype sweeps, interpret-mode vs jnp oracle,
plus hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

try:
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.paged_attention.ops import paged_attention
    from repro.kernels.pagewalk.ops import two_stage_translate
except (ImportError, NotImplementedError, RuntimeError) as e:
    # pallas backend unavailable on this host (real bugs still propagate)
    pytest.skip(f"pallas kernel backend unavailable: {e}",
                allow_module_level=True)


# ---------------------------------------------------------------------------
# pagewalk
# ---------------------------------------------------------------------------

def _random_tables(rng, T=3, R=4, P=16, G=32, slots=40):
    vs = rng.randint(-1, G, size=(T, R, P)).astype(np.int32)
    perm = rng.randint(0, 4, size=(T, R, P)).astype(np.int32)
    g = rng.randint(-1, slots, size=(T, G)).astype(np.int32)
    return vs, perm, g


@pytest.mark.parametrize("B", [1, 7, 512, 513])
def test_pagewalk_kernel_matches_ref_shapes(B):
    rng = np.random.RandomState(B)
    vs, perm, g = _random_tables(rng)
    t = rng.randint(0, 3, B).astype(np.int32)
    r = rng.randint(0, 4, B).astype(np.int32)
    p = rng.randint(0, 16, B).astype(np.int32)
    w = rng.randint(0, 2, B).astype(bool)
    a = two_stage_translate(vs, perm, g, t, r, p, w, force="ref")
    b = two_stage_translate(vs, perm, g, t, r, p, w, force="interpret")
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_pagewalk_kernel_matches_ref_out_of_range():
    """Negative and too-large coordinates, and stage-1 entries past the
    stage-2 table, follow jnp indexing on both paths."""
    rng = np.random.RandomState(7)
    vs, perm, g = _random_tables(rng)
    vs = rng.randint(-1, 32 + 8, size=vs.shape).astype(np.int32)
    B = 256
    t = rng.randint(-5, 3 + 3, B).astype(np.int32)
    r = rng.randint(-6, 4 + 3, B).astype(np.int32)
    p = rng.randint(-20, 16 + 5, B).astype(np.int32)
    w = rng.randint(0, 2, B).astype(bool)
    a = two_stage_translate(vs, perm, g, t, r, p, w, force="ref")
    b = two_stage_translate(vs, perm, g, t, r, p, w, force="interpret")
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 1000))
def test_pagewalk_property_fault_iff_any_stage_invalid(seed):
    rng = np.random.RandomState(seed)
    vs, perm, g = _random_tables(rng)
    B = 64
    t = rng.randint(0, 3, B).astype(np.int32)
    r = rng.randint(0, 4, B).astype(np.int32)
    p = rng.randint(0, 16, B).astype(np.int32)
    w = np.zeros(B, bool)
    slot, fault, stage = two_stage_translate(vs, perm, g, t, r, p, w,
                                             force="ref")
    slot, fault = np.asarray(slot), np.asarray(fault)
    for i in range(B):
        tp = vs[t[i], r[i], p[i]]
        s1_bad = tp < 0 or (perm[t[i], r[i], p[i]] & 1) == 0
        s2_bad = (not s1_bad) and g[t[i], tp] < 0
        assert bool(fault[i]) == (s1_bad or s2_bad)
        if not fault[i]:
            assert slot[i] == g[t[i], tp]


# ---------------------------------------------------------------------------
# paged attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,H,KV,hd,page,n_pages", [
    (2, 4, 1, 16, 8, 4),
    (3, 8, 2, 32, 16, 6),
    (1, 16, 8, 64, 8, 3),
])
def test_paged_attention_matches_ref(B, H, KV, hd, page, n_pages):
    rng = np.random.RandomState(0)
    slots = n_pages * B + 2
    q = jnp.asarray(rng.randn(B, H, hd), jnp.float32)
    kp = jnp.asarray(rng.randn(slots, page, KV, hd), jnp.float32)
    vp = jnp.asarray(rng.randn(slots, page, KV, hd), jnp.float32)
    pm = rng.randint(0, slots, size=(B, n_pages)).astype(np.int32)
    lengths = rng.randint(1, n_pages * page, size=B).astype(np.int32)
    a = paged_attention(q, kp, vp, jnp.asarray(pm), jnp.asarray(lengths),
                        hd ** -0.5, force="ref")
    b = paged_attention(q, kp, vp, jnp.asarray(pm), jnp.asarray(lengths),
                        hd ** -0.5, force="interpret")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               atol=3e-5, rtol=3e-5)


def test_paged_attention_ignores_unmapped_pages():
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(1, 4, 16), jnp.float32)
    kp = jnp.asarray(rng.randn(8, 8, 2, 16), jnp.float32)
    vp = jnp.asarray(rng.randn(8, 8, 2, 16), jnp.float32)
    pm_full = np.array([[0, 1, 2, 3]], np.int32)
    pm_holes = np.array([[0, 1, -1, -1]], np.int32)
    out_full_16 = paged_attention(q, kp, vp, jnp.asarray(pm_full),
                                  jnp.asarray(np.array([16], np.int32)),
                                  0.25, force="ref")
    out_holes_16 = paged_attention(q, kp, vp, jnp.asarray(pm_holes),
                                   jnp.asarray(np.array([16], np.int32)),
                                   0.25, force="ref")
    # tokens 0..15 live in pages 0,1 → identical with/without tail pages
    np.testing.assert_allclose(np.asarray(out_full_16),
                               np.asarray(out_holes_16), rtol=1e-5)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,KV,hd,window,dtype", [
    (1, 64, 2, 1, 16, 0, jnp.float32),
    (2, 128, 4, 2, 32, 0, jnp.float32),
    (1, 128, 4, 4, 32, 32, jnp.float32),
    (2, 256, 8, 2, 64, 0, jnp.bfloat16),
])
def test_flash_attention_matches_ref(B, S, H, KV, hd, window, dtype):
    rng = np.random.RandomState(42)
    q = jnp.asarray(rng.randn(B, S, H, hd), dtype) * 0.5
    k = jnp.asarray(rng.randn(B, S, KV, hd), dtype) * 0.5
    v = jnp.asarray(rng.randn(B, S, KV, hd), dtype)
    a = flash_attention(q, k, v, hd ** -0.5, window, force="ref")
    b = flash_attention(q, k, v, hd ** -0.5, window, force="interpret")
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), atol=tol, rtol=tol)


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 100), bq=st.sampled_from([32, 64]),
       bk=st.sampled_from([32, 128]))
def test_flash_attention_block_size_invariance(seed, bq, bk):
    """Property: output independent of BlockSpec tiling."""
    from repro.kernels.flash_attention.kernel import flash_attention_kernel
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(1, 128, 2, 16), jnp.float32)
    k = jnp.asarray(rng.randn(1, 128, 2, 16), jnp.float32)
    v = jnp.asarray(rng.randn(1, 128, 2, 16), jnp.float32)
    a = flash_attention_kernel(q, k, v, 0.25, 0, bq=bq, bk=bk,
                               interpret=True)
    b = flash_attention_kernel(q, k, v, 0.25, 0, bq=128, bk=128,
                               interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               atol=2e-5, rtol=2e-5)
