"""Every Pallas kernel that the TPU compiler accepts, compiled for a v5e.

Interpret mode (the rest of the kernel suite) cannot see what Mosaic
refuses: unaligned blocks, vector gathers, arrays beyond VMEM.  These
tests compile each kernel ahead of time for one chip of a described
``v5e:2x2`` topology at published widths — dlrm-rm2 ``serve_p99`` (B=512,
d=64, a 13,067,813-slot ROBE array) and dlrm-criteo-tb ``train_batch``
(B=65,536, d=128, 26,135,627 slots) — and check that the program holds
the kernel (``tpu_custom_call``).  Nothing runs, so they say nothing about
results or times.

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
