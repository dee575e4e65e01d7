"""CI hygiene tripwires (ISSUE 2 satellites).

1. ``shard_map`` must be imported from ``deepspeed_tpu.compat`` everywhere
   — the installed JAX may only provide it under ``jax.experimental`` (and
   with a differently-spelled replication-check kwarg), so a direct
   ``from jax import shard_map`` / ``jax.shard_map(...)`` regresses the
   ~80 SPMD tests the shim un-gated.
2. The ``slow`` marker the tier-1 budget depends on (``-m 'not slow'``)
   must stay registered in pyproject.toml.
3. Every ``tests/test_tpu_compile*.py`` takes the described chip and the
   switched-off persistent cache from ``tests/tpu_compile_harness.py``
   and defines neither: the next configuration's file adds buckets and
   assertions, not a seventh copy (and ``tests/conftest.py`` starts the
   files that take ``v5e`` from there first).
"""

import pathlib
import re

REPO = pathlib.Path(__file__).resolve().parent.parent
DIRECT_IMPORT = re.compile(
    r"^\s*(?:from\s+jax(?:\.experimental(?:\.shard_map)?)?\s+import\s+"
    r"(?:[\w,\s]*\bshard_map\b)|.*\bjax\.shard_map\s*\()", re.M)


def _py_sources():
    for root in ("deepspeed_tpu", "tests"):
        for path in sorted((REPO / root).rglob("*.py")):
            if path.name in ("compat.py", "test_marker_audit.py"):
                continue        # the shim itself, and this file's docstring
            yield path


def test_no_direct_shard_map_imports():
    offenders = []
    for path in _py_sources():
        for m in DIRECT_IMPORT.finditer(path.read_text()):
            line = m.group(0).strip()
            if line.startswith("#"):
                continue
            offenders.append(f"{path.relative_to(REPO)}: {line}")
    assert not offenders, (
        "import shard_map from deepspeed_tpu.compat, not jax directly "
        "(see deepspeed_tpu/compat.py):\n" + "\n".join(offenders))


def test_slow_marker_registered():
    pyproject = (REPO / "pyproject.toml").read_text()
    markers = re.search(r"markers\s*=\s*\[(.*?)\]", pyproject, re.S)
    assert markers and "slow" in markers.group(1), (
        "the 'slow' pytest marker must stay registered in pyproject.toml "
        "(the tier-1 suite runs -m 'not slow')")


def test_compile_files_share_one_harness():
    import ast

    files = sorted((REPO / "tests").glob("test_tpu_compile*.py"))
    assert len(files) >= 6
    for path in files:
        body = ast.parse(path.read_text()).body
        taken = {alias.name for node in body
                 if isinstance(node, ast.ImportFrom)
                 and node.module == "tpu_compile_harness"
                 for alias in node.names}
        assert {"v5e", "_no_persistent_cache"} <= taken, (
            f"{path.name} must import v5e and _no_persistent_cache from "
            f"tests/tpu_compile_harness.py")
        own = {node.name for node in body if isinstance(node, ast.FunctionDef)} \
            & {"v5e", "_no_persistent_cache", "_nbytes", "_kernels", "_lowered"}
        assert not own, f"{path.name} defines its own {own}: use the harness"
