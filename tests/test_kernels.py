"""Pallas kernels vs pure-jnp oracles: shape/dtype/Z sweeps (interpret mode),
and the jnp block gather against the element-wise definition."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.robe import RobeSpec, robe_signs, robe_slots
from repro.core.robe import robe_lookup as core_robe_lookup
from repro.kernels import ref
from repro.kernels.ops import dot_interaction, robe_lookup

# ``repro.kernels`` re-exports the op under the module's name
robe_lookup_module = importlib.import_module("repro.kernels.robe_lookup")


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


#: the table of each id column when fields are multi-hot: a field's
#: columns repeat its id, as ``EmbeddingSpec.columns`` gives them
MULTI_HOT_FIELDS = (0, 0, 1, 2, 2, 2)


def _block_gather_case(d, z, dtype, sign, b=13, f=3, tids=None):
    """A prime batch over an array of 3·Z + 5 slots, so that blocks run
    past its end; ids span the 64-bit element index."""
    tids = tuple(range(f)) if tids is None else tids
    rs = np.random.RandomState(d * 1000 + z)
    m = 3 * z + 5
    spec = RobeSpec(size=m, block_size=z, seed=5, use_sign=sign)
    mem = jnp.asarray(rs.randn(m), dtype)
    rows = jnp.asarray(rs.randint(0, 2 ** 31 - 1, (b, len(tids))), jnp.int32)
    want = core_robe_lookup(mem, spec, jnp.asarray(tids, jnp.uint32)[None],
                            rows, d)
    return mem, rows, tids, spec, want


@pytest.mark.parametrize("sign", [False, True])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("d,z,tids", [
    (64, 32, None), (128, 32, None), (32, 32, None), (16, 8, None),
    (16, 32, None), (48, 32, None), (128, 32, MULTI_HOT_FIELDS),
], ids=["64-32", "128-32", "32-32", "16-8", "16-32", "48-32",
        "128-32-multi-hot"])
def test_robe_lookup_block_gather_is_exact(d, z, tids, dtype, sign):
    """The jnp forward gathers whole Z-blocks (Z | d: the blocks end to
    end; Z ∤ d: d elements from inside them) and returns exactly the
    element-wise definition's values, in the memory's dtype, also where
    several id columns belong to one field (multi-hot)."""
    mem, rows, tids, spec, want = _block_gather_case(d, z, dtype, sign,
                                                     tids=tids)
    got = robe_lookup(mem, rows, tids, d, spec, False)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


@pytest.mark.parametrize("d,z", [(64, 32), (48, 32)])
def test_robe_lookup_block_gather_in_pieces_is_exact(monkeypatch, d, z):
    """A batch whose gathered rows pass ``CHUNK_BYTES`` is gathered in
    pieces of rows, the last one shorter: still exact."""
    monkeypatch.setattr(robe_lookup_module, "CHUNK_BYTES", 1 << 14)
    mem, rows, tids, spec, want = _block_gather_case(d, z, jnp.float32,
                                                     True, b=37)
    pieces = -(-37 * 3 * 2 * 128 * 4 // robe_lookup_module.CHUNK_BYTES)
    assert 1 < pieces and 37 % pieces
    got = robe_lookup(mem, rows, tids, d, spec, False)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("b", [1, 100, 130, 300])
def test_robe_lookup_block_gather_batch_sizes(b):
    """From a single row to a batch whose blocks outnumber the array's
    slots, the block gather's table of strided rows gives every block
    exactly."""
    rs = np.random.RandomState(b)
    spec = RobeSpec(size=4099, block_size=32, seed=9)
    mem = jnp.asarray(rs.randn(spec.size), jnp.float32)
    rows = jnp.asarray(rs.randint(0, 2 ** 31 - 1, (b, 1)), jnp.int32)
    want = core_robe_lookup(mem, spec, 0, rows, 64)
    got = robe_lookup(mem, rows, (0,), 64, spec, False)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _lookup_probe():
    """``tools/lookup_probe.py``, which times the forward's formulations
    on a chip, loaded as a module."""
    import importlib.util
    import pathlib
    path = pathlib.Path(__file__).parents[1] / "tools" / "lookup_probe.py"
    spec = importlib.util.spec_from_file_location("lookup_probe", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("name", ["blocks", "blocks-bf16", "a", "b", "f",
                                  "s8", "s16", "s32", "s64"])
def test_lookup_probe_formulations_are_exact(name):
    """Every formulation the probe times returns the element gather's
    values, blocks running past the array's end included."""
    probe = _lookup_probe()
    spec = RobeSpec(size=4099, block_size=32, seed=11)
    vocab = np.array([1000, 50000, 7, 300000])
    mem = jax.random.normal(jax.random.PRNGKey(0), (spec.size,)) * 0.01
    ids = jnp.asarray(probe.draw_ids(vocab, 37, 3))
    want = probe.variant("elem", spec, len(vocab), 64)(mem, ids)
    got = jax.jit(probe.variant(name, spec, len(vocab), 64))(mem, ids)
    assert got.shape == want.shape == (37, 4, 64)
    np.testing.assert_array_equal(
        np.asarray(got, np.float32),
        np.asarray(want.astype(got.dtype), np.float32))


@pytest.mark.parametrize("name", ["blocks-bwd", "sorted-bwd", "s8-bwd",
                                  "s16-bwd", "s32-bwd", "s64-bwd"])
def test_lookup_probe_backward_variants_match_element_scatter(name):
    """Every backward the probe times returns the element scatter's
    gradient of the array to f32 tolerance, blocks running past the
    array's end included."""
    probe = _lookup_probe()
    spec = RobeSpec(size=4099, block_size=32, seed=11)
    vocab = np.array([1000, 50000, 7, 300000])
    mem = jnp.zeros((spec.size,), jnp.float32)
    ids = jnp.asarray(probe.draw_ids(vocab, 37, 3))
    want = np.asarray(probe.variant("elem-bwd", spec, len(vocab), 64)(
        mem, ids))
    got = jax.jit(probe.variant(name, spec, len(vocab), 64))(mem, ids)
    assert got.shape == want.shape == (spec.size,)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                               atol=1e-6 * np.abs(want).max())


def test_robe_lookup_grad_scatter_adds_the_cotangent():
    """The VJP scatter-adds every element's signed cotangent into its
    slot, whichever forward ran."""
    rs = np.random.RandomState(6)
    spec = RobeSpec(size=101, block_size=32, seed=2, use_sign=True)
    mem = jnp.asarray(rs.randn(101), jnp.float32)
    rows = jnp.asarray(rs.randint(0, 2 ** 31 - 1, (7, 3)), jnp.int32)
    ct = rs.randn(7, 3, 64)
    g = jax.grad(lambda m: (robe_lookup(m, rows, (0, 1, 2), 64, spec, False)
                            * ct).sum())(mem)
    tids = jnp.arange(3, dtype=jnp.uint32)[None]
    slots = np.asarray(robe_slots(spec, tids, rows, 64)).reshape(-1)
    signs = np.asarray(robe_signs(spec, tids, rows, 64)).reshape(-1)
    want = np.zeros(101)
    np.add.at(want, slots, ct.reshape(-1) * signs)
    np.testing.assert_allclose(np.asarray(g), want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("d,sign,dtype,ids,m,chunk,fields", [
    (16, False, jnp.float32, "drawn", 4099, None, None),     # d | Z
    (64, False, jnp.float32, "drawn", 4099, None, None),     # Z | d
    (128, False, jnp.float32, "drawn", 4099, None, None),
    (48, False, jnp.float32, "drawn", 4099, None, None),     # Z ∤ d
    (128, True, jnp.float32, "drawn", 4099, None, None),     # signs
    (64, True, jnp.bfloat16, "drawn", 4099, None, None),     # bf16 array
    (128, False, jnp.float32, "equal", 4099, None, None),    # one table row
    (64, True, jnp.float32, "drawn", 101, None, None),       # blocks wrap
    (48, True, jnp.float32, "drawn", 4099, 1 << 14, None),   # in pieces
    (128, True, jnp.float32, "drawn", 4099, None, MULTI_HOT_FIELDS),
], ids=["d16", "d64", "d128", "d48", "sign", "bf16", "equal-ids", "wrap",
        "pieces", "multi-hot"])
def test_robe_lookup_block_scatter_matches_element_scatter(
        monkeypatch, d, sign, dtype, ids, m, chunk, fields):
    """The VJP adds the signed cotangent into the array a whole Z-block
    at a time (the transpose of the block gather): it equals the
    element-by-element scatter-add to f32 tolerance, is summed in f32 and
    delivered in the array's dtype, for any Z and d, a batch of one id
    (each field's blocks all add into the same few table rows), blocks
    that run past the array's end, a batch scattered in pieces, and id
    columns of which several belong to one field (multi-hot)."""
    fields = (0, 1, 2) if fields is None else fields
    b, f, z = 37, len(fields), 32
    if chunk:
        monkeypatch.setattr(robe_lookup_module, "CHUNK_BYTES", chunk)
        assert 1 < -(-b * f * 2 * 128 * 4 // chunk) < b
    rs = np.random.RandomState(d + m)
    spec = RobeSpec(size=m, block_size=z, seed=4, use_sign=sign)
    mem = jnp.asarray(rs.randn(m), dtype)
    rows = (np.full((b, f), 123457) if ids == "equal"
            else rs.randint(0, 2 ** 31 - 1, (b, f)))
    rows = jnp.asarray(rows, jnp.int32)
    # the cotangent arrives in the array's dtype
    ct = np.asarray(jnp.asarray(rs.randn(b, f, d), dtype), np.float64)
    g = jax.jit(jax.grad(lambda mm: (robe_lookup(
        mm, rows, fields, d, spec, False).astype(jnp.float32)
        * ct).sum()))(mem)
    assert g.dtype == dtype and g.shape == (m,)
    tids = jnp.asarray(fields, jnp.uint32)[None]
    slots = np.asarray(robe_slots(spec, tids, rows, d)).reshape(-1)
    signs = (np.asarray(robe_signs(spec, tids, rows, d)).reshape(-1)
             if sign else 1.0)
    want = np.zeros(m)
    np.add.at(want, slots, ct.reshape(-1) * signs)
    # f32 sums in another order; a bf16 gradient is their one rounding
    rtol = 2.0 ** -8 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(g, np.float64), want, rtol=rtol,
                               atol=1e-6 * np.abs(want).max())


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
