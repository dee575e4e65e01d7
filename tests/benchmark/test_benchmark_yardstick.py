"""The yardstick's own arithmetic, checked on the CPU in seconds: traffic is
a pure function of the seed, percentiles and FLOP counts agree with hand
counts, the manifest keeps the contract's static limits, the trace reducer
reads a recorded trace, and the plain references, found by the name a
configuration gives its block, agree with the program's model at a tiny
size."""

import itertools
import json
import math
import os
import shutil

import numpy as np
import pytest

from benchmark import arithmetic as ar
from benchmark import manifest as mf
from benchmark import peaks, readers, scopes, trace, traffic
from benchmark.model import check_consistent
from benchmark.run import Context

BENCH = os.path.dirname(os.path.abspath(traffic.__file__))

CHAT = {"generator": "stratified", "loop": "open",
        "prompt_tokens": {"median": 192, "sigma": 0.8, "min": 32, "max": 768},
        "output_tokens": {"median": 160, "sigma": 0.7, "min": 32, "max": 512},
        "schedule_seed": 0, "preroll_s": 5, "drain_s": 30}


def generator(mix):
    return traffic.generator({"bench_dir": BENCH, "traffic": mix})


def take(mix, seed, n, **kw):
    return list(itertools.islice(
        generator(mix).requests(mix, 50304, seed, **kw), n))


# ---------------------------------------------------------------- traffic

def test_traffic_is_a_pure_function_of_the_seed():
    a, b = take(CHAT, 7, 100, rate_rps=2.0), take(CHAT, 7, 100, rate_rps=2.0)
    assert a == b
    c = take(CHAT, 8, 100, rate_rps=2.0)
    assert [r.prompt for r in a] != [r.prompt for r in c]


def test_the_schedule_is_the_mixs_and_the_tokens_are_the_seeds():
    shape = lambda reqs: [(r.due_s, len(r.prompt), r.new_tokens)
                          for r in reqs]
    a, c = take(CHAT, 1, 64, rate_rps=2.0), take(CHAT, 2, 64, rate_rps=2.0)
    assert shape(a) == shape(c)             # every seed: the same schedule
    other = take(dict(CHAT, schedule_seed=1), 1, 64, rate_rps=2.0)
    assert shape(other) != shape(a)         # another sample: another file
    # every block of 16 offers the same work, whatever the order
    for reqs in (a, other):
        for key in (lambda r: len(r.prompt), lambda r: r.new_tokens):
            assert sorted(map(key, reqs[:16])) == sorted(map(key, a[48:]))
    assert a[15].due_s == pytest.approx(other[15].due_s)
    lens = [len(r.prompt) for r in a]
    assert min(lens) >= 32 and max(lens) <= 768
    assert np.median(lens) == pytest.approx(192, rel=0.05)


def test_an_open_loop_keeps_its_rate_and_exponential_gaps():
    reqs = take(CHAT, 3, 256, rate_rps=4.0)
    assert reqs[-1].due_s == pytest.approx(256 / 4.0, rel=0.05)
    gaps = np.diff([0.0] + [r.due_s for r in reqs])
    assert (gaps > 0).all()
    # an exponential's standard deviation is its mean (the 16 quantiles
    # of a block miss the far tail: a little under)
    assert np.std(gaps) / np.mean(gaps) == pytest.approx(0.9, abs=0.1)


def test_a_closed_loop_has_no_due_times():
    reqs = take(dict(CHAT, loop="closed", clients=4), 5, 32)
    assert all(r.due_s is None for r in reqs)
    assert [r.index for r in reqs] == list(range(32))
    assert sorted(r.new_tokens for r in reqs[:16]) == \
        sorted(r.new_tokens for r in reqs[16:])


@pytest.mark.parametrize("name", ["chat", "doc", "batch", "zero3"])
def test_every_mix_names_a_generator_that_is_there(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        mix = json.load(f)
    module = generator(mix)
    assert hasattr(module, "requests") or hasattr(module, "batches")


def test_an_unknown_generator_is_an_error():
    with pytest.raises(mf.ManifestError):
        generator(dict(CHAT, generator="missing"))


def test_an_open_loop_needs_a_rate():
    with pytest.raises(ValueError):
        take(CHAT, 1, 1)


def test_batches_are_seeded():
    mix = {"generator": "token_batches", "sequence_tokens": 32,
           "sequences_per_chip": 2, "distinct_batches": 3}
    batches = generator(mix).batches
    a, b = batches(mix, 100, 4, 4), batches(mix, 100, 4, 4)
    assert len(a) == 3 and a[0].shape == (8, 33)
    assert all((x == y).all() for x, y in zip(a, b))
    assert not (a[0] == batches(mix, 100, 5, 4)[0]).all()


# ------------------------------------------------------------- arithmetic

@pytest.mark.parametrize("values,p,want", [
    ([1, 2, 3, 4, 5], 50, 3.0),
    ([1, 2, 3, 4, 5], 90, 4.6),          # rank 3.6: 4 + 0.6·(5 − 4)
    ([10, 20], 25, 12.5),
    ([7], 99, 7.0),
    ([1, 2, math.inf], 50, 2.0),
    ([1, 2, math.inf], 90, math.inf),
])
def test_percentile_against_hand_counts(values, p, want):
    assert ar.percentile(values, p) == pytest.approx(want)


def test_latency_arithmetic_against_hand_counts():
    assert ar.ttft_ms(10.0, 10.25) == pytest.approx(250.0)
    assert ar.ttft_ms(10.0, None) == math.inf
    assert ar.tpot_ms([1.0, 1.1, 1.3, 1.6]) == pytest.approx(200.0)
    assert ar.tpot_ms([1.0]) is None
    assert ar.count_in_window([0.9, 1.0, 1.5, 2.0], 1.0, 2.0) == 2
    assert ar.spread([9, 10, 11, 12, 13]) == pytest.approx(2 / 11)
    assert ar.union_seconds([(0, 2), (1, 3), (5, 6)]) == pytest.approx(4)
    assert ar.subtract_seconds([(0, 4)], [(1, 2), (3, 5)]) == pytest.approx(2)
    assert ar.clip_intervals([(0, 5), (7, 9)], 1, 8) == [(1, 5), (7, 8)]


# ------------------------------------------------------------------ peaks

def arch_of(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def block_of(config):
    """The block module a configuration's file names, as a run finds it."""
    return mf.find_block(BENCH, config, "a test")


def test_model_flops_against_hand_counts():
    config = arch_of("mistral-7b")
    mistral, block = config["transformer_config"], block_of(config)
    # a layer: q, o 4096², k, v 4096·1024, three 4096·14336 MLP matrices
    layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    n = mistral["num_layers"] * layer + 4096 * 32000
    assert block.matmul_params(mistral) == n
    per_token = 6 * n + 6 * mistral["num_layers"] * 4096 * 4096
    assert peaks.train_flops_per_token(block, mistral, 4096) == \
        pytest.approx(per_token)
    config = arch_of("pythia-1.4b")
    pythia, block = config["transformer_config"], block_of(config)
    assert block.matmul_params(pythia) == \
        24 * (4 * 2048 * 2048 + 2 * 2048 * 8192) + 2048 * 50304
    # one decode token at context 1000: 2 per weight + 4·H·D·1000 a layer
    assert peaks.forward_flops(block, pythia, 1, 1000) == pytest.approx(
        2 * block.matmul_params(pythia) + 4 * 24 * 2048 * 1000)

    class Sparse:       # the count is the block's: an eighth of it active
        matmul_params = staticmethod(lambda arch: n // 8)

    assert peaks.train_flops_per_token(Sparse, mistral, 4096) == \
        pytest.approx(6 * (n // 8) + 6 * mistral["num_layers"] * 4096 ** 2)


def test_kernel_costs_and_roofline():
    pythia = arch_of("pythia-1.4b")["transformer_config"]
    cost = peaks.paged_attention_cost(pythia, 32, 32 * 500, 32 * 500)
    assert cost["bytes"] == 2 * 16 * 128 * 2 * 16000 + 2 * 16 * 128 * 2 * 32
    assert cost["flops"] == 4 * 16 * 128 * 16000
    # decode is bytes-bound: 131 MB at 819 GB/s
    assert peaks.roofline_seconds(cost, "TPU v5 lite") == \
        pytest.approx(cost["bytes"] / 819e9)
    flash = peaks.flash_attention_cost(
        arch_of("mistral-7b")["transformer_config"], 1, 4096)
    assert flash["flops"] == pytest.approx(3 * 4 * 32 * 128 * 4096 ** 2 / 2)
    assert peaks.roofline_seconds(flash, "TPU v5 lite") == \
        pytest.approx(flash["flops"] / 197e12)


def test_an_unknown_device_kind_is_an_error():
    assert peaks.peaks("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


# --------------------------------------------------------------- manifest

def test_the_manifest_keeps_the_contract():
    manifest = mf.load()
    mf.validate(manifest)
    cells = manifest["workloads"]
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)
    assert manifest["command"] == ["python3", "-m", "benchmark.run"]
    for c in manifest["configs"]:
        check_consistent(arch_of(c["name"]))
    assert len(json.dumps(manifest)) < 64 * 1024


def _name_block(root, name, body=None):
    """Make the first configuration's file (in a copy of the checkout)
    name the block ``name``, whose module holds ``body``."""
    path = os.path.join(root, mf.load(root)["configs"][0]["file"])
    config = dict(arch_of("pythia-1.4b"), block=name)
    if name is None:
        del config["block"]
    with open(path, "w") as f:
        json.dump(config, f)
    if body is not None:
        with open(os.path.join(root, "benchmark", "blocks", name + ".py"),
                  "w") as f:
            f.write(body)


def _one_four_chip_cell_too_many(manifest):
    """Turn one-chip cells into four-chip ones until there is one more
    than a quarter of the cells, rounded down, allows (and one always
    may), however many cells the manifest has by now."""
    cells = manifest["workloads"]
    allowed = max(1, len(cells) // 4)
    have = sum(w["chips"] == 4 for w in cells)
    for w in [w for w in cells if w["chips"] == 1][:allowed + 1 - have]:
        w["chips"] = 4
    assert sum(w["chips"] == 4 for w in cells) == allowed + 1


@pytest.mark.parametrize("how", [
    lambda m, root: m["workloads"][0].update(name="has space"),
    lambda m, root: m["end_to_end"][0].update(unit="tokens per second"),
    lambda m, root: m["end_to_end"][0].update(bound=0.2),
    lambda m, root: _one_four_chip_cell_too_many(m),
    lambda m, root: m["workloads"][0].update(traffic="missing"),  # no file
    lambda m, root: m["per_layer"][0].update(moves="nothing"),
    lambda m, root: m["per_layer"][0].update(why="a key too many"),
    lambda m, root: m["configs"][1].update(reduced=["hidden_size"]),
    lambda m, root: m.update(run_seconds=52),
    lambda m, root: m["end_to_end"].pop(),                  # setup_s gone
    lambda m, root: m["workloads"].append(dict(m["workloads"][0])),
    lambda m, root: _name_block(root, None),
    lambda m, root: _name_block(root, "absent"),
    lambda m, root: _name_block(
        root, "lossless", "logits = matmul_params = lambda *a: 0\n"),
], ids=["name", "unit", "bound", "four-chip-share", "file-by-name", "moves",
        "extra-key", "width-reduced", "run-seconds", "setup_s", "twice",
        "block-unnamed", "block-names-no-file", "block-lacks-loss"])
def test_a_broken_manifest_is_refused(how, tmp_path):
    root = str(tmp_path)
    shutil.copy(os.path.join(mf.CHECKOUT, "BENCHMARK.json"), root)
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    os.makedirs(os.path.join(root, "tests", "benchmark"))
    manifest = mf.load(root)
    mf.validate(manifest, root)             # the copy is whole
    how(manifest, root)
    with pytest.raises((mf.ManifestError, KeyError, FileNotFoundError)):
        mf.validate(manifest, root)


def test_metrics_are_found_per_cell():
    manifest = mf.load()
    names = lambda g, c: {m["name"] for m in mf.metrics_for(manifest, g, c)}
    assert names("end_to_end", "pythia-1.4b.chat") == \
        {"tpot_p90_ms", "setup_s"}
    assert names("end_to_end", "pythia-1.4b.doc") == \
        {"ttft_p90_ms", "tpot_p90_ms", "setup_s"}
    assert names("end_to_end", "mistral-7b.zero3") == \
        {"train_tok_s_chip", "setup_s"}
    assert "coll_share" in names("per_layer", "mistral-7b.zero3")
    assert "coll_share" not in names("per_layer", "mistral-7b.batch")


# ---------------------------------------------------------- trace reducer

def ev(plane, line, name, start, dur):
    return {"plane": plane, "line": line, "name": name, "start": start,
            "dur": dur}


def test_reducer_on_the_recorded_trace():
    """One decode step of pythia-1.4b.chat at [32, 1] on a v5e chip."""
    events = trace.load_recorded(os.path.join(BENCH, "testdata",
                                              "chat_one_step.json"))
    s = trace.summarize(events, chips=1)
    assert s["window_s"] == pytest.approx(0.110012, abs=1e-6)
    assert s["busy_s"] == pytest.approx(0.099934, abs=1e-5)
    # the forward's program ran once, 99.9 ms, dispatched at [32, 1]
    assert trace.module_seconds(s, "forward", tag="32x1") == \
        [pytest.approx(0.0999345, abs=1e-6)]
    assert trace.module_seconds(s, "forward", tag="32x256") == []
    # 24 layers × one Pallas call; self times add up to the busy time
    assert s["kernel_s"] == pytest.approx(0.050302, abs=1e-5)
    assert s["device_ops"][0][0] == "kernel:closed_call"
    assert sum(v for _, v in s["device_ops"]) == \
        pytest.approx(s["busy_s"], rel=0.01)
    # the device waits while scheduler.step works on the host
    assert s["idle_gaps"][0][0] == "bench:step"
    assert sum(v for _, v in s["idle_gaps"]) == \
        pytest.approx(s["window_s"] - s["busy_s"], abs=1e-6)
    assert s["collective_s"] == 0.0


def test_reducer_collectives_overlap_and_gaps():
    d0, d1 = "/device:TPU:0", "/device:TPU:1"
    events = [ev("/host:CPU", "main", "bench:window", 0.0, 10.0),
              ev("/host:CPU", "main", "bench:micro", 0.0, 6.0),
              ev("/host:CPU", "main", "bench:update", 6.0, 1.0)]
    for d in (d0, d1):
        events += [
            ev(d, "XLA Modules", "jit_micro(1)", 1.0, 5.0),
            ev(d, "XLA Ops", "%while.1 = () while(...)", 1.0, 4.0),
            ev(d, "XLA Ops", "%fusion.7 = f32[8] fusion(f32[8] %all-gather.1)",
               1.0, 3.0),                # reads a collective; is compute
            # hidden: runs beside the fusion on the async line
            ev(d, "Async XLA Ops", "%all-gather-start.1 = ...", 2.0, 1.5),
            # exposed: the core waits in the done op, nothing else runs
            ev(d, "XLA Ops", "%all-gather-done.1 = f32[8] ...", 4.0, 1.0),
            ev(d, "XLA Ops", "%reduce-scatter.3 = f32[2] ...", 5.0, 1.0),
            ev(d, "XLA Ops", "%copy.2 = f32[8] copy(...)", 8.0, 1.0)]
    s = trace.summarize(events, chips=2)
    assert s["devices"] == 2 and s["window_s"] == 10.0
    assert s["busy_s"] == pytest.approx(6.0)       # [1, 6) and [8, 9)
    assert s["collective_s"] == pytest.approx(3.5)  # [2, 3.5) + [4, 6)
    assert s["collective_exposed_s"] == pytest.approx(2.0)
    ops = dict(s["device_ops"])
    assert ops["while"] == pytest.approx(0.0)       # its body fills it
    assert ops["fusion"] == pytest.approx(3.0)
    gaps = dict(s["idle_gaps"])
    assert gaps == {"bench:micro": pytest.approx(1.0),      # [0, 1)
                    "bench:update": pytest.approx(1.0),     # [6, 7)
                    "host:other": pytest.approx(2.0)}       # [7, 8), [9, 10)
    assert len(trace.module_seconds(s, "micro")) == 2


def test_a_trace_without_device_work_is_refused():
    with pytest.raises(ValueError):
        trace.summarize([ev("/host:CPU", "main", "bench:window", 0, 1)], 1)


def test_op_family():
    assert trace.op_family("%fusion.12 = f32[8]{0} fusion(f32[8] %p)") == \
        "fusion"
    assert trace.op_family("%copy-done.3.1 = s32[2] copy-done(...)") == \
        "copy-done"
    assert trace.op_family(
        "%closed_call.12 = bf16[32,16] custom-call(s32[32] %x)") == \
        "kernel:closed_call"


def _kernel_context(events, forward_attrs, arch):
    """A traced run's context over recorded events: one ``forward`` span
    of the benchmark's probe, with the put's counts, inside the window."""
    from benchmark.probe import Probe

    probe = Probe()
    probe.spans.append(("forward", 1.0, 1.1, forward_attrs))
    ctx = Context({"xplane": "recorded", "chips": 1, "arch": arch,
                   "probe": probe, "trace_marks": (0.0, 2.0)}, {},
                  {"platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    ctx._trace = trace.summarize(events, chips=1)
    return ctx


def test_a_kernels_roofline_is_over_its_own_events():
    """``paged_attn_roofline`` on the recorded [16, 1] decode step whose
    kernel carries its name (PR 26's): the least time of the step's 24
    calls over the device time of ``kernel:paged_attention`` — what the
    sum over every custom call gave, and still that after a second
    kernel enters the forward."""
    pythia = arch_of("pythia-1.4b")["transformer_config"]
    events = scopes.load_recorded(os.path.join(
        BENCH, "testdata", "chat_one_step_scoped.json"))
    # the record runs on into the next step's first layer: keep one step
    step = min((e for e in events if e["name"].startswith("jit__forward")),
               key=lambda e: e["start"])
    events = [e for e in events if not trace.DEVICE_PLANE.match(e["plane"])
              or e["start"] < step["start"] + step["dur"]]
    stage = min((e for e in events if e["name"] == "ds:stage"),
                key=lambda e: e["start"])["stats"]
    attrs = {k: stage[k] for k in ("valid_tokens", "kv_read_tokens",
                                   "qk_pairs")}
    ctx = _kernel_context(events, attrs, pythia)
    own = [e for e in events if e["name"].startswith("%paged_attention.")]
    assert len(own) == 24                   # one call a layer
    least = 24 * peaks.roofline_seconds(
        peaks.paged_attention_cost(pythia, 15, 5611, 5611), "TPU v5 lite")
    want = 100.0 * least / sum(e["dur"] for e in own)
    got = readers.paged_attention_roofline(ctx)
    assert got == pytest.approx(want, rel=1e-12)
    assert got == pytest.approx(5.25635, rel=1e-5)
    # the parent divided by every custom call of the trace: the same to
    # eight digits (the pool's allocation markers, ~1 ns each, were in)
    assert ctx.trace["kernel_s"] > ctx.trace["kernel_seconds"][
        "kernel:paged_attention"]
    assert got == pytest.approx(100.0 * least / ctx.trace["kernel_s"],
                                rel=1e-7)
    # a second, differently named kernel in every layer of the forward
    other = [dict(e, name=e["name"].replace("%paged_attention.",
                                            "%grouped_matmul."),
                  start=e["start"] + e["dur"] / 4, dur=e["dur"] / 2)
             for e in own]
    crowded = _kernel_context(events + other, attrs, pythia)
    assert crowded.trace["kernel_s"] == pytest.approx(
        1.5 * ctx.trace["kernel_s"], rel=1e-6)
    assert readers.paged_attention_roofline(crowded) == got
    assert dict(crowded.trace["device_ops"])["kernel:grouped_matmul"] > 0
    # a kernel that did not run has nothing to read, and neither has a
    # window that made no call
    assert readers.kernel_roofline(ctx, ("kernel:absent",), least) is None
    assert readers.kernel_roofline(ctx, ("kernel:paged_attention",),
                                   0.0) is None


def test_kernels_are_told_apart_inside_a_program():
    """``per_module``: two executions of a micro step, each holding a
    flash kernel (2 s, then 4 s) and another kernel; the least time of one
    execution over the flash kernel's own time, the median of the two."""
    call = (" custom-call(bf16[8]{0} %q), "
            "custom_call_target=\"tpu_custom_call\"")
    d = "/device:TPU:0"
    events = [ev("/host:CPU", "main", "bench:window", 0.0, 30.0)]
    for start, flash in ((1.0, 2.0), (11.0, 4.0)):
        events += [
            ev(d, "XLA Modules", "jit_micro(1)", start, 8.0),
            ev(d, "XLA Ops", "%flash_attention_fwd.3 = bf16[8]{0}" + call,
               start, flash),
            ev(d, "XLA Ops", "%grouped_matmul.5 = bf16[8]{0}" + call,
               start + flash, 1.0)]
    ctx = Context({"xplane": "made by hand", "chips": 1}, {},
                  {"platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    ctx._trace = trace.summarize(events, chips=1)
    assert ctx.trace["kernel_seconds"] == pytest.approx(
        {"kernel:flash_attention_fwd": 6.0, "kernel:grouped_matmul": 2.0})
    assert readers.kernel_roofline(ctx, readers.FLASH_KERNELS, 1.5,
                                   per_module="micro") == \
        pytest.approx((75.0 + 37.5) / 2)
    assert readers.kernel_roofline(ctx, readers.FLASH_KERNELS, 1.5) == \
        pytest.approx(25.0)


@pytest.mark.parametrize("block_scopes,want", [
    ((), "mlp"), (("router", "experts"), "experts")],
    ids=["shared-vocabulary", "with-the-blocks"])
def test_a_blocks_scopes_extend_the_vocabulary(block_scopes, want):
    op = "jit(_forward)/layers/while/body/closed_call/mlp/experts/dot_general:"
    assert scopes.scope_of(op, block_scopes) == want
    assert scopes.scope_path(op, block_scopes)[:2] == ("layers", "mlp")
    # the scan's plumbing keeps its meaning: under layers, under none of
    # the block's scopes, whichever block
    plumbing = "jit(_forward)/layers/while/body/dynamic_slice:"
    assert scopes.scope_of(plumbing, block_scopes) == scopes.SCAN_OVERHEAD
    events = [
        ev("/host:CPU", "python3", trace.WINDOW, 0.0, 10.0),
        dict(ev("/device:TPU:0", trace.OPS_LINE, "%fusion.1 = f32[8] "
                "fusion(%a)", 1.0, 3.0), op_name=op),
        dict(ev("/device:TPU:0", trace.OPS_LINE, "%copy.2 = f32[8] "
                "copy(%b)", 4.0, 1.0), op_name=plumbing)]

    class Block:
        SCOPES = block_scopes

    ctx = Context({"xplane": "made by hand", "chips": 1},
                  {"block": Block}, {"platform": "tpu", "kind": "x",
                                     "count": 1})
    ctx._trace = {"made": "by hand"}
    ctx._scopes = scopes.summarize(events, 1, Block.SCOPES)
    assert scopes.device_share(ctx, want) == pytest.approx(75.0)
    assert scopes.device_share(ctx, scopes.SCAN_OVERHEAD) == \
        pytest.approx(25.0)


# -------------------------------------------------------------- reference

TINY = {
    "neox": dict(vocab_size=97, hidden_size=64, intermediate_size=128,
                 num_layers=2, num_heads=4, max_seq_len=64, norm="layernorm",
                 norm_eps=1e-5, activation="gelu_exact", position="rope",
                 rope_pct=0.25, rope_theta=10000.0, parallel_residual=True,
                 tie_embeddings=False, use_bias=True),
    "mistral": dict(vocab_size=97, hidden_size=64, intermediate_size=160,
                    num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=64,
                    sliding_window=24, norm="rmsnorm", norm_eps=1e-5,
                    activation="silu", position="rope", rope_pct=1.0,
                    rope_theta=10000.0, parallel_residual=False,
                    tie_embeddings=False, use_bias=False),
}


@pytest.mark.parametrize("block", ["neox", "mistral"])
def test_reference_agrees_with_the_programs_model(block):
    """Both models of the ``dense`` block, found as a run finds it (by
    the name in the configuration's file), float32, perturbed gains and
    biases, a window shorter than the sequence: logits and loss against
    ``CausalLM``."""
    import jax
    import jax.numpy as jnp

    from benchmark.model import seeded_params
    from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig

    arch, reference = TINY[block], block_of({"block": "dense"})
    model = CausalLM(TransformerConfig(dtype=jnp.float32,
                                       attention_impl="reference", **arch))
    params = seeded_params(model, 11, jnp.float32)
    assert float(jnp.abs(params["layers"]["attn_norm_w"] - 1).max()) > 0
    ids = np.random.default_rng(0).integers(0, 97, size=(2, 49))
    want = model.apply(params, jnp.asarray(ids[:, :-1]))
    for row in range(2):
        got = reference.logits(params, jnp.asarray(ids[row, :-1]), arch,
                               q_block=16)
        assert ar.max_rel_err(got, want[row]) < 2e-5
    loss = reference.loss(params, jnp.asarray(ids), arch, q_block=16)
    assert float(loss) == pytest.approx(
        float(model.loss(params, {"input_ids": jnp.asarray(ids)})), rel=1e-5)
    # tight enough to see a lower precision: bf16 weights move the logits
    low = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    got = reference.logits(low, jnp.asarray(ids[0, :-1]), arch)
    assert ar.max_rel_err(got, want[0]) > 1e-3
