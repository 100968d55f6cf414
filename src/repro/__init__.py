"""ROBE / DLRM reproduction in jax, with Pallas TPU kernels.

Importing the package touches no device state, so scripts that must set
XLA_FLAGS (tests/conftest.py, the mesh tests' subprocesses) can still do so
before the first device query.  Entry points call
``repro.launch.cache.use_compile_cache()`` once, before they compile.
"""
