"""Multi-substrate embedding server: all four backends resident at once.

One ``EmbeddingServer`` holds a DLRM scoring model per registered
embedding substrate (full / robe / hashed / tt) resident on the same mesh
— the same trained architecture, four interchangeable embedding layouts —
and routes each request to its substrate through one jitted
``serve_scores`` per backend (through the Pallas lookup and
dot-interaction kernels when ``use_kernel``).

The fetch-bound substrates (``full``/``hashed``) are optionally fronted
by a ``HotRowCache``: the server gathers their hot rows on the host
(bit-exact by the ``cacheable_rows`` contract) and feeds the jitted
scorer precomputed embeddings via the batch's ``"emb"`` key, so switching
the cache on can never change a score.  ``robe`` declines the cache —
the array is already cache-resident, which is the paper's serving claim
and what keeps the full-vs-robe comparison honest.

Batches arrive padded to the compiled shape with ``n_valid`` leading real
rows (the router/``stack_and_pad`` contract): the scorer returns only the
real rows, and the cache never counts the padded tail.

Under an active ``repro.dist`` context the jitted scorers pick up the
mesh through each backend's own ``lookup_dist`` bodies —
the server adds no placement logic of its own.

Each ``score`` call opens three host spans (``jax.profiler.
TraceAnnotation``): ``serve.h2d`` (the batch's copy to the device),
``serve.run`` (the scorer's dispatch) and ``serve.d2h`` (the wait for the
scores and their copy back).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.models import recsys
from repro.models.recsys import RecsysConfig, init_params
from repro.nn.embeddings import get_backend
from repro.serve.hot_cache import HotRowCache
from repro.train import checkpoint as ckpt_lib

__all__ = ["ServerConfig", "EmbeddingServer", "PushReport"]

DEFAULT_BACKENDS = ("full", "robe", "hashed", "tt")


def _scorer(rc: RecsysConfig):
    """``recsys.serve_scores`` bound to one substrate's config, jitted as a
    program of its own that a profile names ``jit_serve_scores``."""

    def serve_scores(params, batch):
        return recsys.serve_scores(params, rc, batch)

    return jax.jit(serve_scores)


@dataclasses.dataclass(frozen=True)
class ServerConfig:
    """One scoring model per substrate, shared architecture: DLRM, or
    DLRM-DCNv2 (``arch``, ``cross_layers``, ``cross_rank``), with one id
    per field or the multi-hot ``bag_sizes``.

    ``robe_compression`` sizes the ROBE array at 1/compression of the full
    table's parameters (the paper's 1000× knob, scaled to taste);
    ``cache_capacity`` rows per cacheable substrate (0 disables the hot
    cache); ``use_kernel`` routes serving through the Pallas lookup
    and dot-interaction kernels (interpret mode off-TPU — slow but
    conformant; no benchmark cell turns it on).
    """

    vocab_sizes: Tuple[int, ...]
    embed_dim: int = 16
    n_dense: int = 8
    bot_mlp: Tuple[int, ...] = ()        # () -> (64, embed_dim)
    top_mlp: Tuple[int, ...] = (64, 1)
    arch: str = "dlrm"                   # "dlrm" | "dlrm_dcnv2"
    cross_layers: int = 0                # dlrm_dcnv2's low-rank cross
    cross_rank: int = 0
    bag_sizes: Tuple[int, ...] = ()      # multi-hot ids per field; ()
    #   = one id per field
    backends: Tuple[str, ...] = DEFAULT_BACKENDS
    robe_compression: int = 1000
    robe_block: int = 32
    use_kernel: bool = False
    cache_capacity: int = 16384
    cache_admit_threshold: int = 1
    sketch_width: int = 1 << 16
    seed: int = 0
    #: default publish dir ``push()`` restores from (an ``OnlineTrainer``'s
    #: ``publish_dir``); per-call ``ckpt_dir`` overrides
    model_dir: Optional[str] = None

    def recsys_cfg(self, backend: str) -> RecsysConfig:
        bot = self.bot_mlp or (64, self.embed_dim)
        n_emb = sum(self.vocab_sizes) * self.embed_dim
        return RecsysConfig(
            name=f"serve-{backend}", arch=self.arch,
            vocab_sizes=self.vocab_sizes, embed_dim=self.embed_dim,
            n_dense=self.n_dense, bot_mlp=bot, top_mlp=self.top_mlp,
            cross_layers=self.cross_layers, cross_rank=self.cross_rank,
            bag_sizes=self.bag_sizes, embedding=backend,
            robe_size=max(512, n_emb // self.robe_compression),
            robe_block=self.robe_block, use_kernel=self.use_kernel)


@dataclasses.dataclass(frozen=True)
class PushReport:
    """What one ``EmbeddingServer.push`` did (the push cell's row reads
    it)."""

    backend: str
    step: int
    kind: str                 # "full" | "delta"
    invalidated: int          # cache rows dropped by the touched manifest
    cache_cleared: bool       # full push (or unanchored delta) → drop all
    wall_s: float


class EmbeddingServer:
    """All substrates resident; ``score(backend, batch, n_valid)`` routes.

    Each substrate gets its own parameters (one ``init_params`` per
    backend off the same seed) and one jitted ``serve_scores``; the jit
    cache keys on the batch's keys, so the cached path (``dense`` +
    ``emb``) and the direct path (``dense`` + ``sparse``) are two traces
    of the same callable.
    """

    def __init__(self, cfg: ServerConfig,
                 params: Optional[Dict[str, dict]] = None):
        self.cfg = cfg
        self._cfgs: Dict[str, RecsysConfig] = {}
        self._params: Dict[str, dict] = {}
        self._jit: Dict[str, callable] = {}
        self._caches: Dict[str, Optional[HotRowCache]] = {}
        for i, name in enumerate(cfg.backends):
            rc = cfg.recsys_cfg(name)
            self._cfgs[name] = rc
            self._params[name] = (params[name] if params is not None
                                  else init_params(
                                      jax.random.PRNGKey(cfg.seed + i), rc))
            self._jit[name] = _scorer(rc)
            cache = None
            if cfg.cache_capacity > 0:
                # the cache gathers through the embedding-layer subtree —
                # the same params ``_embed``'s lookup sees
                cache = HotRowCache.for_backend(
                    get_backend(name), rc.embedding_spec(),
                    self._params[name]["embedding"],
                    capacity=cfg.cache_capacity,
                    sketch_width=cfg.sketch_width,
                    admit_threshold=cfg.cache_admit_threshold,
                    seed=cfg.seed)
            self._caches[name] = cache
        # last publish step applied per backend (None: still on init params)
        self._pushed_step: Dict[str, Optional[int]] = \
            {name: None for name in cfg.backends}

    @property
    def backends(self) -> Tuple[str, ...]:
        return tuple(self.cfg.backends)

    def pushed_step(self, backend: str) -> Optional[int]:
        """Step of the last publish applied (None before any push)."""
        return self._pushed_step[backend]

    def recsys_config(self, backend: str) -> RecsysConfig:
        return self._cfgs[backend]

    def params(self, backend: str) -> dict:
        return self._params[backend]

    def cache(self, backend: str) -> Optional[HotRowCache]:
        return self._caches[backend]

    # -- scoring -----------------------------------------------------------

    def score(self, backend: str, batch: Dict[str, np.ndarray],
              n_valid: Optional[int] = None, *,
              use_cache: bool = True) -> np.ndarray:
        """Route one padded batch to ``backend``; returns [n_valid] scores.

        ``batch``: ``{"dense": [B, n_dense], "sparse": [B, ΣL]}`` (numpy
        or jax; one id column per field unless ``cfg.bag_sizes``).  With a
        hot cache resident for this substrate (and ``use_cache``), the
        sparse gather happens host-side through the
        cache and the jitted scorer receives precomputed ``"emb"`` — the
        scores are bit-identical either way (``cacheable_rows`` contract).
        """
        if backend not in self._cfgs:
            raise KeyError(f"backend {backend!r} not resident; serving: "
                           f"{sorted(self._cfgs)}")
        cache = self._caches[backend] if use_cache else None
        if cache is not None:
            feats = {"dense": batch["dense"],
                     "emb": cache.lookup(np.asarray(batch["sparse"]),
                                         n_valid)}
        else:
            feats = {"dense": batch["dense"], "sparse": batch["sparse"]}
        with TraceAnnotation("serve.h2d"):
            jb = {k: jnp.asarray(v) for k, v in feats.items()}
        with TraceAnnotation("serve.run"):
            scores = self._jit[backend](self._params[backend], jb)
        with TraceAnnotation("serve.d2h"):
            out = np.asarray(scores)
        return out[:n_valid] if n_valid is not None else out

    def score_fn(self, backend: str, *, use_cache: bool = True):
        """A ``score_fn(batch, n_valid=...)`` closure for the router /
        ``MicroBatcher`` / replay harness, bound to one substrate."""

        def fn(batch, n_valid=None):
            return self.score(backend, batch, n_valid, use_cache=use_cache)

        fn.__name__ = f"score_{backend}"
        return fn

    # -- zero-downtime model push -------------------------------------------

    def push(self, backend: str, step: Optional[int] = None, *,
             ckpt_dir: Optional[str] = None) -> PushReport:
        """Hot-swap ``backend``'s params to a published checkpoint.

        Restores the publish at ``step`` (newest when None) from
        ``ckpt_dir`` (default ``cfg.model_dir``) via
        ``checkpoint.restore_delta``, swaps the parameter tree in one
        assignment, and reconciles the hot cache:

        * delta publish whose chain anchors at this server's last applied
          step → ``invalidate`` exactly the union of touched rows for
          chain entries past that anchor (untouched entries survive,
          bit-exact by the delta contract);
        * full publish, first push, or an unanchored chain (the server
          skipped past a full base) → ``clear`` — nothing bounds what
          changed, so everything must refetch.

        The swap itself is atomic with respect to a dispatching
        ``AsyncRouter``/replay loop (scoring is synchronous between
        micro-batches; see ``AsyncRouter.apply``): in-flight batches
        complete on the old params, the next dispatched batch scores on
        the new ones, and no batch ever sees a mix.
        """
        t0 = time.perf_counter()
        ckpt_dir = ckpt_dir if ckpt_dir is not None else self.cfg.model_dir
        if ckpt_dir is None:
            raise ValueError("push: no ckpt_dir given and cfg.model_dir "
                             "is unset")
        restored = ckpt_lib.restore_delta(ckpt_dir, self._params[backend],
                                          step=step)
        if restored is None:
            raise FileNotFoundError(
                f"push: no restorable publish in {ckpt_dir}"
                + (f" at step {step}" if step is not None else ""))
        tree, manifest = restored
        new_params = jax.tree.map(jnp.asarray, tree)
        new_step = int(manifest["step"])
        last = self._pushed_step[backend]

        invalidated, cleared = 0, False
        cache = self._caches[backend]
        if cache is not None:
            anchors = {int(manifest.get("base_full_step", new_step))}
            anchors.update(int(c["step"]) for c in manifest.get("chain", []))
            if manifest.get("delta") and last is not None and last in anchors:
                for c in manifest["chain"]:
                    if int(c["step"]) > last:
                        invalidated += cache.invalidate_manifest(c["touched"])
            else:
                cache.clear()
                cleared = True
            cache.set_params(new_params["embedding"])

        self._params[backend] = new_params
        self._pushed_step[backend] = new_step
        return PushReport(backend=backend, step=new_step,
                          kind="delta" if manifest.get("delta") else "full",
                          invalidated=invalidated, cache_cleared=cleared,
                          wall_s=time.perf_counter() - t0)

    # -- cache bookkeeping --------------------------------------------------

    def cache_stats(self, backend: str) -> Optional[dict]:
        cache = self._caches[backend]
        return None if cache is None else cache.stats()

    def warm_caches(self, id_batches: Sequence[np.ndarray]) -> None:
        """Pre-heat every resident cache from prior traffic ids."""
        for cache in self._caches.values():
            if cache is not None:
                cache.warm(id_batches)

    def reset_cache_stats(self) -> None:
        for cache in self._caches.values():
            if cache is not None:
                cache.reset_stats()

    def reset_caches(self) -> None:
        """Full cold-start reset of every resident cache — store, sketch
        heat, and counters (``HotRowCache.reset``).  The benchmark grid
        calls this between cells so no cell's traffic distribution leaks
        into the next one's resident set or admission heat."""
        for cache in self._caches.values():
            if cache is not None:
                cache.reset()
