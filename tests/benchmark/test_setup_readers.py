"""The six start-up readers (benchmark/setup_readers.py, one file each
under benchmark/layer_metrics/): the manifest's entries, what each reads
off the chip through a cell's rehearsal, how the parts stand to each other
and to ``setup_s``, and that a program without the recorder reads nothing."""

import math
import sys

import pytest
from test_benchmark_runners import checkout, rehearse  # noqa: F401  (a fixture)

from benchmark import manifest as mf
from benchmark import run as bench_run
from benchmark import setup_readers

SECONDS = ("setup_trace_s", "setup_lower_s", "setup_compile_s",
           "setup_build_wall_s", "setup_gc_s")
NEW_METRICS = SECONDS + ("setup_cache_hit_share",)


def test_the_manifest_appends_the_six_entries():
    manifest = mf.load()
    mf.validate(manifest)
    cells = [w["name"] for w in manifest["workloads"]]
    entries = manifest["per_layer"][-6:]
    assert tuple(m["name"] for m in entries) == NEW_METRICS
    for m in entries:
        assert (m["layer"], m["moves"], m["source"]) == (
            "start-up", "setup_s", "program_counter")
        seconds = m["name"] in SECONDS
        assert (m["unit"], m["better"]) == (
            ("s", "lower") if seconds else ("%", "higher"))
        # zero3 compiles uncached by design: no share of hits to read
        assert m["workloads"] == [c for c in cells
                                  if seconds or c != "mistral-7b.zero3"]
    assert "setup_s" in {m["name"] for m in manifest["end_to_end"]}
    assert not any(m["moves"] == "setup_s"
                   for m in manifest["per_layer"][:-6])


@pytest.fixture()
def contexts(monkeypatch):
    """The ``Context`` objects a run hands its readers, kept."""
    made = []

    class Kept(bench_run.Context):
        def __init__(self, *args):
            super().__init__(*args)
            made.append(self)

    monkeypatch.setattr(bench_run, "Context", Kept)
    return made


@pytest.mark.parametrize("cell", ["pythia-1.4b.chat", "mistral-7b.zero3"])
def test_a_cell_rehearsed_reads_every_part(checkout, capsys, contexts,  # noqa: F811
                                           cell):
    line, extra = rehearse(checkout, capsys, cell, 1)
    assert line["correct"], extra["why_not"]
    wanted = [m for m in NEW_METRICS
              if cell in next(e for e in mf.load()["per_layer"]
                              if e["name"] == m)["workloads"]]
    assert len(wanted) == (5 if cell == "mistral-7b.zero3" else 6)
    got = {m: line["metrics"][m]["value"] for m in wanted}
    assert all(math.isfinite(v) and v >= 0 for v in got.values())
    assert all(line["metrics"][m]["unit"] == "s" for m in SECONDS)
    setup_s = contexts[0].result["setup_s"]
    stages = got["setup_trace_s"] + got["setup_lower_s"] \
        + got["setup_compile_s"]
    assert 0 < got["setup_build_wall_s"] <= stages + 1e-6
    assert got["setup_build_wall_s"] <= setup_s
    assert got["setup_gc_s"] <= setup_s
    if "setup_cache_hit_share" in got:
        assert 0 <= got["setup_cache_hit_share"] <= 100
    # read once a run, whichever reader asks first
    assert setup_readers.parts(contexts[0]) is contexts[0]._setup_parts


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_program_without_the_recorder_reads_nothing(monkeypatch, name):
    import deepspeed_tpu.telemetry as telemetry

    monkeypatch.delattr(telemetry, "builds", raising=False)
    monkeypatch.setitem(sys.modules, "deepspeed_tpu.telemetry.builds", None)
    ctx = bench_run.Context({"window": (10.0, 60.0), "setup_s": 5.0}, {}, {})
    assert mf.find_module(mf.HERE, "layer_metrics", name).reduce(ctx) is None


def test_a_run_that_asked_no_cache_reads_no_share():
    ctx = bench_run.Context({}, {}, {})
    ctx._setup_parts = {"cache_hits": 0, "cache_misses": 0}
    assert setup_readers.cache_hit_share(ctx) is None
    ctx._setup_parts = {"cache_hits": 3, "cache_misses": 1}
    assert setup_readers.cache_hit_share(ctx) == 75.0
