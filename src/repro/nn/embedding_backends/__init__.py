"""Pluggable embedding substrates behind one ``EmbeddingBackend`` protocol.

``get_backend(name)`` dispatches to the registered backend; importing this
package registers the four shipped substrates:

* ``full``   — uncompressed concatenated table, row-sharded over `model`
               (or the whole mesh with ``placement="2d"``)
* ``robe``   — the paper's shared ROBE array (replicated, or `model`-
               sharded ZeRO-3 style with ``placement="model"``)
* ``hashed`` — QR compositional hashing-trick baseline
* ``tt``     — tensor-train factorized tables (TT-Rec baseline)
* ``qrobe``  — the ROBE array stored as int8 + learned per-group scales,
               dequantized inside the lookup kernel (ALPT-style QAT)

See ``base.py`` for the protocol and ``repro.nn.embeddings`` for the
spec + convenience wrappers the models call.
"""

from repro.nn.embedding_backends.base import (EmbeddingBackend,
                                              backend_names, get_backend,
                                              register_backend)
from repro.nn.embedding_backends import full as _full        # noqa: F401
from repro.nn.embedding_backends import robe as _robe        # noqa: F401
from repro.nn.embedding_backends import hashed as _hashed    # noqa: F401
from repro.nn.embedding_backends import tt as _tt            # noqa: F401
from repro.nn.embedding_backends import qrobe as _qrobe      # noqa: F401
from repro.nn.embedding_backends.full import full_lookup_sharded_body
from repro.nn.embedding_backends.robe import robe_allgather_body

__all__ = ["EmbeddingBackend", "get_backend", "register_backend",
           "backend_names", "full_lookup_sharded_body",
           "robe_allgather_body"]
