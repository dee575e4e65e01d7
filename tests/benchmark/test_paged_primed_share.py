"""``paged_primed_share`` (benchmark/layer_metrics/paged_primed_share.py)
on hand-made contexts: the program's ``attn_steps_primed`` over
``attn_steps``, summed over the ``forward`` spans that began inside the
traced marks; a program whose spans carry no such counts (the parent's)
and an untraced run read nothing."""

import pytest

from benchmark import manifest as mf


class _Ctx:
    def __init__(self, records, marks=(10.0, 100.0), names=None):
        names = names or ["forward"] * len(records)
        self.result = {
            "trace_marks": marks,
            "program_spans": [{"name": name, "t_start": 5.0 + 10 * i,
                               "attrs": r}
                              for i, (name, r) in enumerate(zip(names,
                                                                records))]}


def steps(live, primed, **more):
    return {"bucket_chunk": 1, "attn_steps": live,
            "attn_steps_primed": primed, **more}


def reduce(ctx):
    return mf.find_module(mf.HERE, "layer_metrics",
                          "paged_primed_share").reduce(ctx)


def test_the_counts_are_summed_over_the_forwards_inside_the_marks():
    # the first forward began before the marks; a merged forward's two
    # calls (one chunk step, 31 ones of which 30 follow a live step) count
    # as the program summed them; ``stage`` spans are not read
    records = [steps(32, 31), steps(32, 31), steps(1 + 31, 0 + 30),
               steps(1, 0), steps(24, 23), steps(999, 999)]
    names = ["forward"] * 5 + ["stage"]
    assert reduce(_Ctx(records, names=names)) == pytest.approx(
        100.0 * (31 + 30 + 0 + 23) / (32 + 32 + 1 + 24))


def test_the_manifest_names_the_metric_for_the_batch_cell():
    entry, = [m for m in mf.load()["per_layer"]
              if m["name"] == "paged_primed_share"]
    assert entry == {"name": "paged_primed_share", "unit": "%",
                     "better": "higher", "source": "program_counter",
                     "layer": "kernels", "moves": "serve_tok_s",
                     "workloads": ["mistral-7b.batch"]}
    assert mf.load()["per_layer"][-1] == entry


@pytest.mark.parametrize("case", ["parent", "untraced", "no_marks",
                                  "nothing_walked", "before_the_marks"])
def test_nothing_to_read_is_none(case):
    records = [steps(32, 31)] * 4
    marks = (10.0, 100.0)
    if case == "parent":        # forwards without the counts
        records = [{"bucket_chunk": 1, "kv_blocks_live": 300}] * 4
    if case == "nothing_walked":
        records = [steps(0, 0)] * 4
    if case == "before_the_marks":
        marks = (500.0, 600.0)
    ctx = _Ctx(records, marks=None if case == "no_marks" else marks)
    if case == "untraced":
        ctx.result["program_spans"] = []
    assert reduce(ctx) is None
