"""``paged_unmasked_turn_share``
(benchmark/layer_metrics/paged_unmasked_turn_share.py) on hand-made
contexts: the program's ``attn_turns_unmasked`` over ``attn_turns``, summed
over the ``forward`` spans that began inside the traced marks; a program
whose spans carry no such counts (the parent's) and an untraced run read
nothing."""

import pytest

from benchmark import manifest as mf

NAME = "paged_unmasked_turn_share"


class _Ctx:
    def __init__(self, records, marks=(10.0, 100.0), names=None):
        names = names or ["forward"] * len(records)
        self.result = {
            "trace_marks": marks,
            "program_spans": [{"name": name, "t_start": 5.0 + 10 * i,
                               "attrs": r}
                              for i, (name, r) in enumerate(zip(names,
                                                                records))]}


def turns(folded, unmasked, **more):
    return {"bucket_chunk": 2048, "attn_steps": 64, "attn_steps_primed": 63,
            "attn_turns": folded, "attn_turns_unmasked": unmasked, **more}


def reduce(ctx):
    return mf.find_module(mf.HERE, "layer_metrics", NAME).reduce(ctx)


def test_the_counts_are_summed_over_the_forwards_inside_the_marks():
    # the first forward began before the marks; a windowed chunk's pieces
    # (17 turns a step, 13 of them seen by every row), one-token forwards
    # whose last turn holds the row's own key; ``stage`` spans are not read
    records = [turns(1088, 832), turns(1088, 832), turns(72, 64),
               turns(8, 0), turns(4, 3), turns(999, 999)]
    names = ["forward"] * 5 + ["stage"]
    assert reduce(_Ctx(records, names=names)) == pytest.approx(
        100.0 * (832 + 64 + 0 + 3) / (1088 + 72 + 8 + 4))


def test_the_manifest_names_the_metric_for_the_chunked_cells():
    manifest = mf.load()
    mf.validate(manifest)
    entry, = [m for m in manifest["per_layer"] if m["name"] == NAME]
    assert entry == {"name": NAME, "unit": "%", "better": "higher",
                     "source": "program_counter", "layer": "kernels",
                     "moves": "ttft_p90_ms",
                     "workloads": ["qwen3-next-80b-a3b.longdoc",
                                   "trinity-large-preview.mixedctx"]}
    # appended behind what the manifest held, and both cells are judged
    # on the metric it moves
    names = [m["name"] for m in manifest["per_layer"]]
    assert names.index(NAME) > names.index("setup_cache_hit_share")
    judged, = [m for m in manifest["end_to_end"]
               if m["name"] == entry["moves"]]
    assert set(entry["workloads"]) <= set(judged["workloads"])


@pytest.mark.parametrize("case", ["parent", "untraced", "no_marks",
                                  "nothing_folded", "before_the_marks"])
def test_nothing_to_read_is_none(case):
    records = [turns(17, 13)] * 4
    marks = (10.0, 100.0)
    if case == "parent":        # forwards with the steps and not the turns
        records = [{"bucket_chunk": 1, "attn_steps": 32,
                    "attn_steps_primed": 31}] * 4
    if case == "nothing_folded":
        records = [turns(0, 0)] * 4
    if case == "before_the_marks":
        marks = (500.0, 600.0)
    ctx = _Ctx(records, marks=None if case == "no_marks" else marks)
    if case == "untraced":
        ctx.result["program_spans"] = []
    assert reduce(ctx) is None
