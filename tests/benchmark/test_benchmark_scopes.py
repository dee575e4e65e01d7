"""``benchmark/scopes.py`` — the reader of the program's own names — checked
on the CPU: its arithmetic on a hand-made trace, the decoder against a
trace this process records, a recorded chip trace (``testdata/``), what
every new reader gives off the chip, and, in a rehearsal of the serve
runner, that the program's ``stage`` spans say what the benchmark's own
wrappers (``probe.py``) say, forward by forward."""

import argparse
import os
import time

import pytest
from test_benchmark_runners import checkout  # noqa: F401  (a fixture)

from benchmark import manifest as mf
from benchmark import scopes, trace
from benchmark.run import Context

NEW_SERVE = ("dev_scan_overhead_share", "dev_kv_write_share",
             "dev_unscoped_share", "step_pack_ms", "step_stage_ms",
             "step_commit_ms", "idle_unspanned_share")
NEW_TRAIN = ("train_fwd_share", "train_bwd_share", "train_remat_share",
             "train_opt_share", "train_unscoped_share")
RECORDED = os.path.join(mf.HERE, "testdata", "chat_one_step_scoped.json")


def test_scope_and_pass_of_an_op_name():
    fwd = "jit(_forward)/layers/while/body/closed_call/"
    assert scopes.scope_of(fwd + "kv_write/scatter:") == "kv_write"
    assert scopes.scope_of(fwd + "attend/paged_attention/pallas_call:") \
        == "attend"
    assert scopes.scope_of("jit(_forward)/layers/while/body/dynamic_slice:") \
        == scopes.SCAN_OVERHEAD
    assert scopes.scope_of("jit(_forward)/logits/dot_general:") == "logits"
    assert scopes.scope_of("jit(dynamic_slice)/dynamic_slice:") \
        == scopes.scope_of("") == scopes.UNSCOPED
    # JAX wraps a scope in the transformation it was traced under
    micro = "jit(micro)/loss_and_grad/"
    bwd = micro + "transpose(jvp(layers))/while/body/closed_call/checkpoint/"
    assert scopes.scope_path(bwd + "mlp/dot_general") == \
        ("loss_and_grad", "layers", "mlp")
    for op_name, module, want in (
            (micro + "jvp(layers)/while/body/closed_call/qkv/dot_general",
             "jit_micro(1)", "fwd"),
            (bwd + "mlp/dot_general", "jit_micro(1)", "bwd"),
            (bwd + "rematted_computation/mlp/dot_general", "jit_micro(1)",
             "remat"),
            ("jit(micro)/grad_accumulate/add", "jit_micro(1)", "bwd"),
            ("", "jit_micro(1)", "unscoped"),
            ("jit(update)/optimizer/cond/branch_0_fun/mul", "jit_update(2)",
             "opt"),
            ("", "jit_update(2)", "opt")):
        assert scopes.train_pass(op_name, module) == want, op_name


def _ev(line, name, start, dur, plane="/device:TPU:0", **extra):
    return dict(plane=plane, line=line, name=name, start=start, dur=dur,
                **extra)


def test_shares_and_idle_phases_of_a_hand_made_trace():
    """10 s window. Device: a program from 1 to 7 whose while (2..6) holds
    a qkv op (2..3), a kernel under attend (3..5) and nothing from 5 to 6
    (the while's own second); an unscoped copy (6..7); then idle. Host:
    ds:step 0.5..9 with ds:stage 0.5..1.5 and ds:fetch 1.5..8 inside."""
    layers = "jit(_forward)/layers/while"
    call = " custom-call(bf16[8]{0} %q), custom_call_target=\"tpu_custom_call\""
    events = [
        _ev("python3", trace.WINDOW, 0.0, 10.0, plane="/host:CPU"),
        _ev(trace.MODULES_LINE, "jit__forward(1)", 1.0, 6.0),
        _ev(trace.OPS_LINE, "%while.4 = (s32[]) while(%t)", 2.0, 4.0,
            op_name=layers + ":"),
        _ev(trace.OPS_LINE, "%fusion.7 = bf16[8]{0} fusion(%a)", 2.0, 1.0,
            op_name=layers + "/body/closed_call/qkv/dot_general:"),
        _ev(trace.OPS_LINE, "%paged_attention.1 = bf16[8]{0}" + call, 3.0,
            2.0, op_name=layers + "/body/closed_call/attend/paged_attention"
            "/pallas_call:"),
        _ev(trace.OPS_LINE, "%copy.3 = bf16[8]{0} copy(%b)", 6.0, 1.0,
            op_name=""),
        _ev("python3", "ds:step", 0.5, 8.5, plane="/host:CPU", stats={}),
        _ev("python3", "ds:stage", 0.5, 1.0, plane="/host:CPU",
            stats={"bucket_seqs": 16}),
        _ev("python3", "ds:fetch", 1.5, 6.5, plane="/host:CPU", stats={}),
    ]
    s = scopes.summarize(events, chips=1)
    assert s["busy_s"] == pytest.approx(5.0) and s["scoped"] and s["spanned"]
    assert dict(s["by_scope"]) == pytest.approx(
        {"qkv": 1.0, "attend": 2.0, scopes.SCAN_OVERHEAD: 1.0,
         scopes.UNSCOPED: 1.0})
    assert dict(s["unscoped_ops"]) == pytest.approx({"copy": 1.0})
    assert dict(s["by_pass"]) == pytest.approx({"fwd": 4.0, "unscoped": 1.0})
    # idle: 0..2 and 7..10, cut at the spans' edges, innermost span wins
    assert dict(s["idle_by_phase"]) == pytest.approx(
        {scopes.UNSPANNED: 0.5 + 1.0, "ds:stage": 1.0, "ds:fetch": 0.5 + 1.0,
         "ds:step": 1.0})
    assert s["idle_s"] == pytest.approx(5.0)

    ctx = _context({"xplane": "unused", "chips": 1}, "tpu")
    ctx._trace, ctx._scopes = {"made": "by hand"}, s
    read = lambda name: mf.find_module(mf.HERE, "layer_metrics",
                                       name).reduce(ctx)
    assert read("dev_scan_overhead_share") == pytest.approx(20.0)
    assert read("dev_kv_write_share") == 0.0
    assert read("sat_dev_unscoped_share") == pytest.approx(20.0)
    assert read("idle_unspanned_share") == pytest.approx(30.0)
    # a trace of a program without the names: nothing to read, no error
    bare = [dict(e, op_name="") if "op_name" in e else e
            for e in events if not e["name"].startswith("ds:")]
    ctx._scopes = scopes.summarize(bare, chips=1)
    assert [read(n) for n in NEW_SERVE[:3] + NEW_SERVE[-1:] + NEW_TRAIN] \
        == [None] * 9


def test_the_train_passes_sum_to_the_busy_time():
    loss = "jit(micro)/loss_and_grad/"
    body = "(layers))/while/body/closed_call/checkpoint/"
    ops = [("fwd", 3.0, loss + "jvp(layers)/while/body/closed_call/mlp/dot:"),
           ("remat", 2.0, loss + "transpose(jvp" + body +
            "rematted_computation/mlp/dot:"),
           ("bwd", 4.0, loss + "transpose(jvp" + body + "mlp/dot:"),
           ("bwd", 0.5, "jit(micro)/grad_accumulate/add:"),
           ("unscoped", 0.5, "")]
    events = [_ev("python3", trace.WINDOW, 0.0, 20.0, plane="/host:CPU"),
              _ev(trace.MODULES_LINE, "jit_micro(1)", 1.0, 10.0),
              _ev(trace.MODULES_LINE, "jit_update(2)", 12.0, 2.0),
              _ev(trace.OPS_LINE, "%fusion.9 = f32[8]{0} fusion(%g)", 12.0,
                  2.0, op_name="jit(update)/optimizer/mul:")]
    at = 1.0
    for _, dur, op_name in ops:
        events.append(_ev(trace.OPS_LINE, "%fusion.1 = f32[8]{0} fusion(%a)",
                          at, dur, op_name=op_name))
        at += dur
    s = scopes.summarize(events, chips=1)
    assert dict(s["by_pass"]) == pytest.approx(
        {"fwd": 3.0, "remat": 2.0, "bwd": 4.5, "unscoped": 0.5, "opt": 2.0})
    ctx = _context({"xplane": "unused", "chips": 1}, "tpu")
    ctx._trace, ctx._scopes = {"made": "by hand"}, s
    shares = [mf.find_module(mf.HERE, "layer_metrics", n).reduce(ctx)
              for n in NEW_TRAIN]
    assert shares == pytest.approx([25.0, 37.5, 100 / 6, 100 / 6, 100 / 24])
    assert sum(shares) == pytest.approx(100.0)


def test_the_recorded_step_reduces_to_its_scopes():
    """One decode step at [16,1] cut from a chip trace of this PR: the
    table against sums taken here straight from the file's leaf events."""
    events = scopes.load_recorded(RECORDED)
    s = scopes.summarize(events, chips=1)
    table = dict(s["by_scope"])
    ops = [e for e in events if e["line"] == trace.OPS_LINE]
    whiles = [e for e in ops if e["name"].startswith("%while")]
    assert len(whiles) == 1 and whiles[0]["op_name"] == ""
    # back-to-back operations: busy time is the sum of what does not
    # enclose another operation, plus the while's own 1.6 us
    leaves = [e for e in ops if e is not whiles[0]]
    assert s["busy_s"] == pytest.approx(sum(e["dur"] for e in leaves),
                                        rel=1e-4)
    assert s["busy_s"] == pytest.approx(0.078046, rel=1e-4)
    for scope, want in (("kv_write", 0.010236), ("attend", 0.026795),
                        (scopes.SCAN_OVERHEAD, 0.037859), ("mlp", 0.002196)):
        mine = sum(e["dur"] for e in leaves
                   if scopes.scope_of(e["op_name"]) == scope)
        assert table[scope] == pytest.approx(mine, rel=1e-6)
        assert table[scope] == pytest.approx(want, rel=1e-3)
    assert 100 * table[scopes.SCAN_OVERHEAD] / s["busy_s"] == \
        pytest.approx(48.5, abs=0.1)
    assert table[scopes.UNSCOPED] < 1e-5
    # the kernel is found by its name, under its scope
    program = min((e for e in events if e["line"] == trace.MODULES_LINE
                   and e["name"].startswith("jit__forward")),
                  key=lambda e: e["start"])
    kernel = [e for e in ops if trace.op_family(e["name"])
              == "kernel:paged_attention"
              and e["start"] < program["start"] + program["dur"]]
    assert len(kernel) == 24 and all(      # one a layer
        e["op_name"].endswith("/attend/paged_attention/pallas_call:")
        for e in kernel)
    # idle: 7.19 ms of the 85 ms, nearly all of it named by a ds:* span
    idle = dict(s["idle_by_phase"])
    assert s["idle_s"] == pytest.approx(s["window_s"] - s["busy_s"])
    assert idle["ds:fetch"] > idle["ds:pack"] > idle["ds:stage"] \
        > idle["ds:commit"] > idle[scopes.UNSPANNED]
    assert idle[scopes.UNSPANNED] / s["idle_s"] == pytest.approx(0.0413,
                                                                abs=1e-3)
    stage = [e for e in events if e["name"] == "ds:stage"]
    assert stage[0]["stats"] == {
        "bucket_seqs": 16, "bucket_chunk": 1, "rows": 15, "valid_tokens": 15,
        "kv_read_tokens": 5611, "qk_pairs": 5611, "free_blocks": 241}


def test_the_decoder_reads_what_the_profiler_writes(tmp_path):
    """A trace this process records on the CPU: the window mark and the
    program's annotation with its stats, on the clock ProfileData reads."""
    import glob

    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.telemetry import Tracer

    tracer = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            with tracer.span("stage", trace_id="replica-0") as span:
                jnp.ones((8, 8)).block_until_ready()
                span.attrs.update(bucket_seqs=16, note="x")
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    events = scopes.load(path)
    mark, = [e for e in events if e["name"] == trace.WINDOW]
    stage, = [e for e in events if e["name"] == "ds:stage"]
    assert stage["stats"] == {"bucket_seqs": 16, "note": "x"}
    assert mark["start"] <= stage["start"] and \
        stage["start"] + stage["dur"] <= mark["start"] + mark["dur"] + 1e-9
    theirs, = [e for e in trace.load_xplane(path)
               if e["name"] == trace.WINDOW]
    assert mark["start"] == pytest.approx(theirs["start"], abs=2e-9)
    assert mark["dur"] == pytest.approx(theirs["dur"], abs=2e-9)


def _context(result, platform):
    return Context(result, {}, {"platform": platform, "kind": "x",
                                "count": 1})


def test_every_new_reader_returns_none_off_the_chip():
    spans = [{"name": n, "t_start": 1.0, "t_end": 1.5, "attrs": {}}
             for n in ("pack", "stage", "commit")]
    result = {"xplane": "/nonexistent.xplane.pb", "chips": 1,
              "window": (0.0, 10.0), "program_spans": spans}
    manifest = mf.load()
    names = {m["name"] for m in manifest["per_layer"]}
    new = NEW_SERVE + tuple("sat_" + n for n in NEW_SERVE) + NEW_TRAIN
    assert len(new) == 19 and set(new) <= names
    ctx = _context(result, "cpu")
    for name in new:
        assert mf.find_module(mf.HERE, "layer_metrics", name).reduce(ctx) \
            is None, name
    # on the chip the span readers read the program's spans
    ctx = _context(result, "tpu")
    ctx._trace = {"made": "by hand"}
    assert mf.find_module(mf.HERE, "layer_metrics",
                          "step_stage_ms").reduce(ctx) == pytest.approx(500.0)
    ctx.result["program_spans"] = []        # a program without the spans
    assert mf.find_module(mf.HERE, "layer_metrics",
                          "sat_step_pack_ms").reduce(ctx) is None


def test_program_stage_spans_agree_with_the_probes_wrappers(checkout):  # noqa: F811
    """The serve runner rehearsed with ``--trace 1``: forward by forward,
    the attrs of the program's ``stage`` span are what the benchmark's
    wrapper round ``paged.forward`` computed from ``engine.batch``, and
    ``free_blocks`` is what it sampled after the put — the agreement a
    later PR needs to retire ``probe.py``."""
    from benchmark import device as dev
    from benchmark import serve_runner

    info = mf.resolve(mf.load(checkout), "pythia-1.4b.chat", checkout)
    args = argparse.Namespace(
        seed=11, seconds=1.0, trace=1,
        trace_dir=os.path.join(checkout, "chiprun_out", "traces", "t"))
    result = serve_runner.run(info, args, dev.CompileWatch(),
                              time.monotonic())
    assert result["correct"], result["why_not"]
    probe = result["probe"]
    steps = [(t0, t1) for name, t0, t1, _ in probe.spans if name == "step"]
    in_step = lambda t: any(a <= t <= b for a, b in steps)
    # the logits check after the window calls engine.put outside any step
    forwards = [a for name, t0, _, a in probe.spans
                if name == "forward" and in_step(t0)]
    free = [v for t, name, v in probe.samples
            if name == "free_blocks" and in_step(t)]
    stages = [s["attrs"] for s in result["program_spans"]
              if s["name"] == "stage"]
    assert len(stages) == len(forwards) == len(free) > 10
    for mine, theirs, blocks in zip(stages, forwards, free):
        assert mine == {"bucket_seqs": theirs["seqs"],
                        "bucket_chunk": theirs["chunk"],
                        "rows": theirs["rows"],
                        "valid_tokens": theirs["valid_tokens"],
                        "kv_read_tokens": theirs["kv_read_tokens"],
                        "qk_pairs": theirs["qk_pairs"],
                        "free_blocks": blocks}
    # and each step holds its phases, in order
    spans = result["program_spans"]
    by_id = {s["span_id"]: s for s in spans}
    for s in spans:
        if s["name"] in ("pack", "stage", "fetch", "commit"):
            assert by_id[s["parent_id"]]["name"] == "step"
    assert {"admit_inbox", "idle_wait"} <= {s["name"] for s in spans}
