"""Pallas kernels vs pure-jnp oracles: shape/dtype/Z sweeps (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.robe import RobeSpec
from repro.kernels import ref
from repro.kernels.ops import dot_interaction, robe_lookup


@pytest.mark.parametrize("b,f,d,z,sign,dtype", [
    (8, 4, 16, 16, False, jnp.float32),     # aligned Z == d
    (8, 4, 16, 32, True, jnp.float32),      # aligned Z > d, signs
    (16, 3, 8, 64, False, jnp.float32),     # aligned Z >> d
    (4, 1, 128, 128, False, jnp.float32),   # single wide field (LM-like)
    (8, 4, 16, 4, False, jnp.float32),      # general Z < d
    (8, 2, 16, 1, True, jnp.float32),       # ROBE-1 (feature hashing)
    (6, 5, 10, 16, False, jnp.float32),     # general, d ∤ Z
    (8, 4, 16, 16, False, jnp.bfloat16),    # bf16 memory
    (8, 4, 16, 2, True, jnp.bfloat16),
])
def test_robe_lookup_kernel_vs_oracle(b, f, d, z, sign, dtype):
    rs = np.random.RandomState(0)
    spec = RobeSpec(size=4096, block_size=z, seed=7, use_sign=sign)
    mem = jnp.asarray(rs.randn(4096), dtype)
    rows = jnp.asarray(rs.randint(0, 10**6, (b, f)), jnp.int32)
    tids = jnp.arange(f, dtype=jnp.uint32)
    want = ref.robe_lookup_ref(mem, rows, tids, d, spec)
    got = robe_lookup(mem, rows, tuple(range(f)), d, spec, True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=1e-6)


def test_robe_lookup_kernel_grad_matches_ref_grad():
    rs = np.random.RandomState(1)
    spec = RobeSpec(size=512, block_size=16, seed=2, use_sign=True)
    mem = jnp.asarray(rs.randn(512), jnp.float32)
    rows = jnp.asarray(rs.randint(0, 1000, (4, 3)), jnp.int32)
    ct = jnp.asarray(rs.randn(4, 3, 16), jnp.float32)

    def loss_k(m):
        return (robe_lookup(m, rows, (0, 1, 2), 16, spec, True) * ct).sum()

    def loss_r(m):
        return (ref.robe_lookup_ref(
            m, rows, jnp.arange(3, dtype=jnp.uint32), 16, spec) * ct).sum()

    np.testing.assert_allclose(np.asarray(jax.grad(loss_k)(mem)),
                               np.asarray(jax.grad(loss_r)(mem)),
                               rtol=1e-5, atol=1e-6)


def test_robe_lookup_grad_dtype_matches_memory_dtype():
    """Custom-VJP contract: the memory cotangent carries the memory's dtype
    (bf16 ROBE arrays previously got a silently-f32 gradient)."""
    rs = np.random.RandomState(4)
    spec = RobeSpec(size=512, block_size=16, seed=3, use_sign=True)
    rows = jnp.asarray(rs.randint(0, 1000, (4, 3)), jnp.int32)
    ct = jnp.asarray(rs.randn(4, 3, 16), jnp.float32)
    for dtype in (jnp.float32, jnp.bfloat16):
        mem = jnp.asarray(rs.randn(512), dtype)
        g = jax.grad(lambda m: (robe_lookup(m, rows, (0, 1, 2), 16, spec,
                                            False).astype(jnp.float32)
                                * ct).sum())(mem)
        assert g.dtype == dtype, (g.dtype, dtype)
    # bf16 grad values match the f32 reference within bf16 resolution
    mem32 = jnp.asarray(rs.randn(512), jnp.float32)
    want = jax.grad(lambda m: (robe_lookup(m, rows, (0, 1, 2), 16, spec,
                                           False) * ct).sum())(mem32)
    got = jax.grad(lambda m: (robe_lookup(m, rows, (0, 1, 2), 16, spec,
                                          False).astype(jnp.float32)
                              * ct).sum())(mem32.astype(jnp.bfloat16))
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), rtol=0.05, atol=0.05)


@pytest.mark.parametrize("b,z", [
    (13, 16),       # Z < d: eight blocks per row
    (13, 128),      # Z > d: one block per row
    (1, 16),        # degenerate batch: one short grid step
])
def test_robe_lookup_kernel_prime_batch_pads_tile(b, z):
    """Prime batch sizes must not degrade the grid to one-row tiles: the
    kernel tiles (row, field) pairs at PAIRS_PER_STEP, pads the last tile
    and slices the output back.  b·f is sized so that b=13 spans two grid
    steps, the second one padded."""
    from repro.kernels.tiling import PAIRS_PER_STEP
    f, d = 100, 128
    assert PAIRS_PER_STEP < 13 * f < 2 * PAIRS_PER_STEP   # pads step 2
    rs = np.random.RandomState(5)
    spec = RobeSpec(size=4096, block_size=z, seed=7, use_sign=True)
    mem = jnp.asarray(rs.randn(4096), jnp.float32)
    rows = jnp.asarray(rs.randint(0, 10**6, (b, f)), jnp.int32)
    want = ref.robe_lookup_ref(mem, rows, jnp.arange(f, dtype=jnp.uint32),
                               d, spec)
    got = robe_lookup(mem, rows, tuple(range(f)), d, spec, True)
    assert got.shape == (b, f, d)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_pick_batch_tile_no_prime_degradation():
    from repro.kernels.tiling import pick_batch_tile
    # prime batch: tile stays large (pad-and-slice), never collapses to 1
    assert pick_batch_tile(8191, 26, 64) > 1
    assert pick_batch_tile(8192, 26, 64) == pick_batch_tile(8191, 26, 64)
    # tiny batches are clamped to the batch rounded up to 8 sublanes
    assert pick_batch_tile(3, 4, 16) == 8


def test_robe_lookup_wraps_circularly():
    """Rows whose blocks land near |M| must wrap, matching the oracle."""
    spec = RobeSpec(size=260, block_size=64, seed=0)   # wraps often
    mem = jnp.arange(260, dtype=jnp.float32)
    rows = jnp.arange(32, dtype=jnp.int32)[:, None]
    want = ref.robe_lookup_ref(mem, rows, jnp.zeros(1, jnp.uint32), 32, spec)
    got = robe_lookup(mem, rows, (0,), 32, spec, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("b,f,d,self_i,dtype", [
    (8, 27, 16, False, jnp.float32),        # DLRM kaggle shape
    (16, 27, 64, False, jnp.float32),       # dlrm-rm2 interaction
    (4, 8, 16, True, jnp.float32),
    (8, 12, 32, False, jnp.bfloat16),
])
def test_dot_interaction_kernel_vs_oracle(b, f, d, self_i, dtype):
    rs = np.random.RandomState(2)
    feats = jnp.asarray(rs.randn(b, f, d), dtype)
    want = ref.dot_interaction_ref(feats, self_i)
    got = dot_interaction(feats, self_i, use_kernel=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5,
                               atol=1e-2 if dtype == jnp.bfloat16 else 1e-5)


def test_cin_ref_consistency():
    """CIN oracle: explicit z-tensor contraction matches the fused einsum."""
    rs = np.random.RandomState(3)
    x0 = jnp.asarray(rs.randn(4, 6, 8), jnp.float32)
    xk = jnp.asarray(rs.randn(4, 5, 8), jnp.float32)
    w = jnp.asarray(rs.randn(7, 6, 5), jnp.float32)
    got = ref.cin_layer_ref(x0, xk, w)
    z = np.einsum("bid,bjd->bijd", np.asarray(x0), np.asarray(xk))
    want = np.einsum("hij,bijd->bhd", np.asarray(w), z)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4,
                               atol=1e-5)


from hypothesis import given, settings, strategies as st


@settings(max_examples=20, deadline=None)
@given(b=st.integers(min_value=1, max_value=12),
       f=st.integers(min_value=1, max_value=6),
       log_d=st.integers(min_value=2, max_value=6),
       log_z=st.integers(min_value=0, max_value=7),
       sign=st.booleans())
def test_robe_lookup_kernel_hypothesis_sweep(b, f, log_d, log_z, sign):
    """Property sweep: kernel == oracle for arbitrary (B,F,d,Z,sign)."""
    d, z = 2 ** log_d, 2 ** log_z
    rs = np.random.RandomState(b * 100 + f)
    spec = RobeSpec(size=2048, block_size=z, seed=5, use_sign=sign)
    mem = jnp.asarray(rs.randn(2048), jnp.float32)
    rows = jnp.asarray(rs.randint(0, 2 ** 30, (b, f)), jnp.int32)
    want = ref.robe_lookup_ref(mem, rows, jnp.arange(f, dtype=jnp.uint32),
                               d, spec)
    got = robe_lookup(mem, rows, tuple(range(f)), d, spec, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
