"""Registry-coverage gate: nothing registers without tests following it.

Two drift failure modes this file pins down:

* a new ``jax.custom_vjp`` op lands in ``kernels/ops.py`` without a
  conformance ``_case()`` triple — its kernel/jnp/grad parity would go
  untested until something downstream breaks;
* a new embedding backend registers without joining the shared parity
  suite (``tests/test_embedding_backends.py``), so the whole-table
  reference / kernel / gradient checks silently skip it.

Both checks are structural (AST + module attributes), so they stay cheap
and run in the lint CI job alongside ``ruff``.
"""

import ast
import importlib.util
import pathlib

from repro.nn.embedding_backends import backend_names

ROOT = pathlib.Path(__file__).resolve().parent.parent


def _custom_vjp_ops(path: pathlib.Path):
    """Names of top-level functions in `path` decorated with custom_vjp."""
    tree = ast.parse(path.read_text(), filename=str(path))
    ops = []
    for node in ast.iter_child_nodes(tree):
        if not isinstance(node, ast.FunctionDef):
            continue
        for dec in node.decorator_list:
            if "custom_vjp" in ast.dump(dec):
                ops.append(node.name)
    return ops


def _load_test_module(name: str):
    spec = importlib.util.spec_from_file_location(
        f"_coverage_{name}", ROOT / "tests" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_every_custom_vjp_op_has_a_conformance_case():
    ops = _custom_vjp_ops(ROOT / "src" / "repro" / "kernels" / "ops.py")
    assert len(ops) >= 5, ops       # robe/qrobe/dot/qr/tt today
    conformance = (ROOT / "tests" / "test_kernel_conformance.py").read_text()
    missing = [op for op in ops if op not in conformance]
    assert not missing, (
        f"custom_vjp ops with no conformance-suite coverage: {missing} — "
        f"add a _case() branch in tests/test_kernel_conformance.py")


def test_conformance_cases_cover_every_op_family():
    """The CASES tuple itself must grow with the op registry: an op that is
    merely *imported* by the conformance file but never exercised as a case
    would pass the substring check above."""
    mod = _load_test_module("test_kernel_conformance")
    ops = _custom_vjp_ops(ROOT / "src" / "repro" / "kernels" / "ops.py")
    # each case name is a family keyed off its op prefix (robe_lookup →
    # "robe", dot_interaction → "dot", qr_lookup → "qr", ...)
    families = {op.split("_")[0] for op in ops}
    assert families <= set(mod.CASES), (
        f"op families {families - set(mod.CASES)} missing from "
        f"test_kernel_conformance.CASES")


def test_every_registered_backend_is_in_parity_suite():
    mod = _load_test_module("test_embedding_backends")
    registered = set(backend_names())
    suite = set(mod.BACKENDS)
    assert suite == registered, (
        f"parity suite BACKENDS {sorted(suite)} != registry "
        f"{sorted(registered)} — register_backend() calls must be matched "
        f"by an entry in tests/test_embedding_backends.BACKENDS")
