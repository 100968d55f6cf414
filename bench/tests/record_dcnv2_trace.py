#!/usr/bin/env python3
"""Record the trace of the served DLRM-DCNv2 program that
``test_new_cells.py`` reads, on the chip.

    python3 bench/tests/record_dcnv2_trace.py <out_dir>

The registry's ``dlrm-dcnv2`` at its published widths (26 fields, 214
multi-hot ids a row, d = 128, 3 cross layers of rank 512) but a ROBE array
of 2**20 slots, served by ``EmbeddingServer`` with the program's own
weights: one call of 512 rows to compile, then three under the profiler.
The ``*.xplane.pb`` is copied to ``<out_dir>/dcnv2.xplane.pb``.
"""

import glob
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_arch  # noqa: E402
from repro.serve.server import EmbeddingServer, ServerConfig  # noqa: E402

ROWS, SLOTS = 512, 1 << 20


def main(out_dir: str) -> int:
    cfg = get_arch("dlrm-dcnv2").make_config()
    n_emb = sum(cfg.vocab_sizes) * cfg.embed_dim
    server = EmbeddingServer(ServerConfig(
        vocab_sizes=cfg.vocab_sizes, embed_dim=cfg.embed_dim,
        n_dense=cfg.n_dense, bot_mlp=cfg.bot_mlp, top_mlp=cfg.top_mlp,
        arch=cfg.arch, cross_layers=cfg.cross_layers,
        cross_rank=cfg.cross_rank, bag_sizes=cfg.bag_sizes,
        backends=("robe",), robe_compression=n_emb // SLOTS))
    rng = np.random.default_rng(0)
    vocab = np.asarray(cfg.vocab_sizes)[list(cfg.embedding_spec().columns)]
    batch = {"dense": rng.normal(size=(ROWS, cfg.n_dense)).astype(np.float32),
             "sparse": (rng.random((ROWS, len(vocab))) ** 2
                        * vocab).astype(np.int32)}
    server.score("robe", batch)
    d = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("score"):
            server.score("robe", batch)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
    os.makedirs(out_dir, exist_ok=True)
    out = os.path.join(out_dir, "dcnv2.xplane.pb")
    shutil.copy(path, out)
    shutil.rmtree(d)
    print(os.path.getsize(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
