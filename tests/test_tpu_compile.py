"""Every Pallas kernel that the TPU compiler accepts, compiled for a v5e.

Interpret mode (the rest of the kernel suite) cannot see what Mosaic
refuses: unaligned blocks, vector gathers, arrays beyond VMEM.  These
tests compile each kernel ahead of time for one chip of a described
``v5e:2x2`` topology at published widths — dlrm-rm2 ``serve_p99`` (B=512,
d=64, a 13,067,813-slot ROBE array) and dlrm-criteo-tb ``train_batch``
(B=65,536, d=128, 26,135,627 slots) — and check that the program holds
the kernel (``tpu_custom_call``); and the whole serve program of the
``dcnv2-bulk`` benchmark cell.  Nothing runs, so they say nothing about
results or times on the chip.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU compiler's library, and every
test worker imports this file.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch

WIDTHS = {
    # arch, batch of the registry shape
    "dlrm-rm2/serve_p99": ("dlrm-rm2", 512),
    "dlrm-criteo-tb/train_batch": ("dlrm-criteo-tb", 65536),
}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler can be loaded here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "the kernel is not in the program"


def _recsys(width):
    arch, batch = WIDTHS[width]
    cfg = get_arch(arch).make_config()
    return cfg, batch, len(cfg.vocab_sizes), cfg.embed_dim


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_robe_lookup_compiles_for_v5e(one_chip, width):
    from repro.kernels.robe_lookup import robe_lookup_pallas
    cfg, b, f, d = _recsys(width)
    spec = cfg.embedding_spec().robe
    _compile(functools.partial(robe_lookup_pallas, table_ids=tuple(range(f)),
                               dim=d, spec=spec, interpret=False),
             _shape((spec.size,), jnp.float32, one_chip),
             _shape((b, f), jnp.int32, one_chip))


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_robe_lookup_block_gather_compiles_for_v5e(one_chip, width):
    """The default (jnp) forward: the compiler keeps it a gather of whole
    table rows, one per Z-block, and gathers no single slots."""
    from repro.kernels.ops import robe_lookup
    cfg, b, f, d = _recsys(width)
    spec = cfg.embedding_spec().robe
    tids = tuple(range(f))
    text = jax.jit(lambda m, r: robe_lookup(m, r, tids, d, spec, False)).lower(
        _shape((spec.size,), jnp.float32, one_chip),
        _shape((b, f), jnp.int32, one_chip)).compile().as_text()
    gathers = [line for line in text.splitlines() if " gather(" in line]
    assert any("slice_sizes={1,128}" in line and "robe_blocks" in line
               for line in gathers), gathers
    assert not [line for line in gathers if "slice_sizes={1}" in line]


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_dot_interaction_compiles_for_v5e(one_chip, width):
    from repro.kernels.dot_interaction import dot_interaction_pallas
    _, b, f, d = _recsys(width)
    _compile(functools.partial(dot_interaction_pallas, self_interaction=False,
                               interpret=False),
             _shape((b, f + 1, d), jnp.float32, one_chip))


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_qrobe_lookup_compiles_for_v5e(one_chip, width):
    from repro.kernels.robe_lookup import qrobe_lookup_pallas
    from repro.nn.embedding_backends.qrobe import GROUP_LOG2
    cfg, b, f, d = _recsys(width)
    spec = cfg.embedding_spec().robe
    groups = -(-spec.size // (1 << GROUP_LOG2))
    _compile(functools.partial(qrobe_lookup_pallas,
                               table_ids=tuple(range(f)), dim=d, spec=spec,
                               group_log2=GROUP_LOG2, interpret=False),
             _shape((spec.size,), jnp.int8, one_chip),
             _shape((groups,), jnp.float32, one_chip),
             _shape((b, f), jnp.int32, one_chip))


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_qr_lookup_compiles_for_v5e(one_chip, width):
    from repro.kernels.qr_lookup import qr_lookup_pallas
    from repro.nn.embedding_backends.hashed import default_buckets, qr_layout
    cfg, b, f, d = _recsys(width)
    m = default_buckets(cfg.vocab_sizes)
    q_rows, q_off, r_off = qr_layout(cfg.vocab_sizes, m)
    _compile(functools.partial(qr_lookup_pallas,
                               q_off=tuple(int(o) for o in q_off),
                               r_off=tuple(int(o) for o in r_off), m=m,
                               interpret=False),
             _shape((int(np.sum(q_rows)), d), jnp.float32, one_chip),
             _shape((m * f, d), jnp.float32, one_chip),
             _shape((b, f), jnp.int32, one_chip))


#: a compile of the ``dcnv2-bulk`` serve program that takes longer points
#: at a relayout whose code generation takes minutes (PERF.md §7); it took
#: 7.9–8.6 s on the CPU that describes the chip
DCNV2_COMPILE_S = 120


def test_dcnv2_bulk_serve_program_compiles_for_v5e(one_chip):
    """The whole DLRM-DCNv2 serve program of the ``dcnv2-bulk`` cell
    (16,384 rows of 214 multi-hot ids, the 26,135,627-slot array, 3 cross
    layers of rank 512), with the default jnp lookup: it compiles for one
    v5e chip within ``DCNV2_COMPILE_S``, fits its 16 GB, and gathers whole
    blocks."""
    import time

    from repro.models.recsys import init_params
    from repro.serve.server import _scorer
    cfg = get_arch("dlrm-dcnv2").make_config()
    assert cfg.n_columns == 214
    params = jax.tree.map(
        lambda x: _shape(x.shape, x.dtype, one_chip),
        jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0)))
    batch = {"dense": _shape((16384, 13), jnp.float32, one_chip),
             "sparse": _shape((16384, 214), jnp.int32, one_chip)}
    t0 = time.perf_counter()
    compiled = _scorer(cfg).lower(params, batch).compile()
    took = time.perf_counter() - t0
    print(f"dcnv2-bulk serve program: compiled in {took:.1f} s")
    assert took < DCNV2_COMPILE_S
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9
    text = compiled.as_text()
    assert any("slice_sizes={1,128}" in line and "robe_blocks" in line
               for line in text.splitlines() if " gather(" in line)


def test_refused_kernels_raise_on_tpu(monkeypatch):
    """A kernel Mosaic refuses raises on a TPU with the compiler's reason —
    it is never swapped for interpret mode or the jnp path there."""
    from repro.kernels import ops
    assert set(ops.REFUSED_ON_TPU) == {"tt_lookup"}
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    with pytest.raises(NotImplementedError, match="Only 2D gather"):
        ops.tt_lookup(jnp.zeros((2, 2, 2)), jnp.zeros((2, 2, 2, 2)),
                      jnp.zeros((2, 2, 2)), jnp.zeros((1, 1), jnp.int32),
                      (0,), (2, 2, 2), 8, True)
    assert ops._interpret("robe_lookup") is False
