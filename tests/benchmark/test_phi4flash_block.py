"""The ``phi4flash`` block as the benchmark finds it: the manifest with
its entries, the configuration against the catalog row it was drawn from
(nothing reduced), the issue's arithmetic, the reference — every layer on
every position — against the program's model at the tiny twin's size
(``CausalLM.apply``, and prefill in chunks then decode through the pools
and the slots, where the serving forward leaves the cross-decoder out for
the positions nobody reads), switches thrown the other way failing, the
scope names, the new readers on hand-made contexts, and the cell rehearsed
end to end on the CPU under the real names. The twin's layout is the
published one at twelve layers: (mamba1, window) x 3, (mamba1, full) x 1,
(gmu, cross) x 2."""

import json
import os

import numpy as np
import pytest
from test_benchmark_runners import (_read, _write, check_line,  # noqa: F401
                                    checkout, rehearse)

from benchmark import manifest as mf
from benchmark import scopes, xdec_readers
from benchmark.model import check_consistent

CELL, CONFIG = "phi-4-mini-flash-reasoning.deepthink", \
    "phi-4-mini-flash-reasoning"
NEW_READERS = ("cross_attn_share", "gmu_share", "logits_share",
               "xdec_prefill_share", "xdec_rows_share",
               "shared_kv_read_gbps", "paged_attn_diff_roofline")
LISTED = ("attn_window_share", "attn_full_share", "kv_resident_ratio",
          "kv_window_blocks_peak_share", "kv_full_blocks_peak_share",
          "state_slots_peak_share", "mamba_share", "mamba_scan_share",
          "mamba_state_io_share", "ssm_state_gbps",
          "paged_unmasked_turn_share", "setup_trace_s", "setup_lower_s",
          "setup_compile_s", "setup_build_wall_s", "setup_gc_s",
          "setup_cache_hit_share", "batch_seqs_mean", "pad_ratio",
          "fwd_decode_dev_ms", "fwd_mixed_dev_ms", "dev_decode_ms_per_forward",
          "dev_prefill_us_per_token", "decode_time_chunk_share",
          "queue_wait_p50_ms", "gen_late_p99_ms", "host_step_share")
CATALOG_FILE = "/opt/skills/guides/model-configs/architectures.jsonl"
CATALOG_NAME = "Phi-4-mini-flash-reasoning"
TWINS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "twins")
PROMPT, STEPS = 90, 5


def block():
    return mf.find_module(mf.HERE, "blocks", "phi4flash")


def real():
    manifest = mf.load()
    return manifest, mf.resolve(manifest, CELL)


def twin():
    return _read(os.path.join(TWINS, "configs", CONFIG + ".json"))


# ------------------------------------------------------------ the manifest

def test_the_manifest_validates_with_the_new_entries():
    manifest, info = real()
    mf.validate(manifest)
    assert len(manifest["workloads"]) >= 12
    assert info["block"].__name__.endswith("phi4flash")
    traffic = info["traffic"]
    assert (traffic["generator"], traffic["loop"],
            traffic["schedule_seed"]) == ("stratified", "open", 0)
    assert traffic["prompt_tokens"] == {
        "median": 4096, "sigma": 1.0, "min": 256, "max": 32768}
    assert traffic["output_tokens"] == {
        "median": 1024, "sigma": 0.6, "min": 256, "max": 3072}
    assert (traffic["preroll_s"], traffic["drain_s"]) == (30, 90)
    assert info["cell"]["chips"] == 1 and info["workload"]["serving"] == {}
    assert 0 < info["workload"]["rate_rps"]
    assert info["workload"]["trace_s"] <= 2
    ends = {m["name"] for m in mf.metrics_for(manifest, "end_to_end", CELL)}
    assert ends == {"ttft_p90_ms", "tpot_p90_ms", "setup_s"}
    mine = {m["name"] for m in mf.metrics_for(manifest, "per_layer", CELL)}
    assert set(NEW_READERS) | set(LISTED) <= mine
    # one roofline, the paged kernel's with the block's own cost function:
    # no accepted reader's FLOPs and bytes are this model's least work
    # (PERF.md section 3), and the PR writes no kernel
    assert {m for m in mine if "roofline" in m or m == "mfu"} \
        == {"paged_attn_diff_roofline"}
    assert not mine & {"experts_share", "moe_route_share", "gdn_share",
                       "lightning_share", "latent_attn_share"}
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert all(by_name[name]["moves"] in ends for name in mine)
    for name in NEW_READERS:
        assert by_name[name]["workloads"][0] == CELL
    assert {by_name[n]["moves"] for n in ("xdec_prefill_share",
                                          "xdec_rows_share")} \
        == {"ttft_p90_ms"}
    assert {by_name[n]["moves"] for n in (
        "cross_attn_share", "gmu_share", "logits_share",
        "shared_kv_read_gbps", "paged_attn_diff_roofline")} \
        == {"tpot_p90_ms"}
    at = lambda group, name: [e["name"] for e in manifest[group]  # noqa: E731
                              ].index(name)
    assert at("configs", CONFIG) > at("configs", "smallthinker-21b-a3b")
    assert at("workloads", CELL) > at("workloads",
                                      "smallthinker-21b-a3b.bulkgen")
    assert at("per_layer", "cross_attn_share") > at(
        "per_layer", "paged_unmasked_turn_share")


def test_the_configuration_is_the_catalog_rows_and_nothing_is_reduced():
    _, info = real()
    config, entry = info["config"], info["config_entry"]
    with open(CATALOG_FILE) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == CATALOG_NAME)
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == [] and config["reduced"] == {}
    for key, value in row["config"].items():
        assert config[key] == value, key
    b = info["block"]
    for c in (config, twin()):
        check_consistent(c, b)
        arch = c["transformer_config"]
        assert arch["layer_runs"] == b.mb_per_layer_runs(
            c["num_hidden_layers"], c["mb_per_layer"])
        assert arch["num_layers"] == c["num_hidden_layers"]
        assert arch["head_size"] * arch["num_heads"] == arch["hidden_size"]
        assert arch["attn_scale"] == pytest.approx(arch["head_size"] ** -0.5)
        assert arch["rope_kinds"] == []         # assumed.no_position_term
        assert (arch["norm"], arch["qkv_bias"], arch["o_bias"],
                arch["diff_attn"], arch["tie_embeddings"]) == (
                    "layernorm", True, True, True, True)
    arch = config["transformer_config"]
    assert arch["layer_runs"] == [[["mamba1", "window"], 8],
                                  [["mamba1", "full"], 1],
                                  [["gmu", "cross"], 7]]
    assert (arch["mamba1_inner_size"], arch["mamba1_state_size"],
            arch["mamba1_dt_rank"], arch["mamba1_conv_kernel"]) == (
                2 * 2560, 16, -(-2560 // 16), 4)
    for key in ("_provenance", "weights", "layer_layout", "s6_sizes",
                "s6_form", "state_dtype", "gmu", "differential_attention",
                "biases", "no_position_term", "window_rule",
                "initialisation", "positions_run", "left_out"):
        assert config["assumed"][key], key
    assert "whole" in config["deployment"]
    check = config["check"]
    assert check["min_prompt_tokens"] > config["engine"]["max_chunk_tokens"]
    assert check["min_prompt_tokens"] < check["max_prompt_tokens"]
    tw = twin()["transformer_config"]
    assert set(tw) == set(arch)
    assert all(tw[k] == arch[k] for k in arch
               if isinstance(arch[k], (bool, str)) and k != "dtype")
    assert [p for p, _ in tw["layer_runs"]] \
        == [p for p, _ in arch["layer_runs"]]


def test_the_arithmetic_is_the_issues():
    _, info = real()
    b, arch = info["block"], info["config"]["transformer_config"]
    M = 1e6
    assert b.layer_kinds(arch) == {"mamba1": 9, "window": 8, "full": 1,
                                   "gmu": 7, "cross": 7}
    mixers = b.mixer_matmul_params(arch)
    assert mixers["mamba1"] / M == pytest.approx(41.1, abs=0.1)
    assert mixers["full"] / M == pytest.approx(19.7, abs=0.05)
    assert mixers["cross"] / M == pytest.approx(13.1, abs=0.05)
    assert mixers["gmu"] / M == pytest.approx(26.2, abs=0.05)
    # a decode token meets all 32 layers; a prompt token that is not read
    # 17 layers, layer 17's W_k and W_v and no head
    assert b.matmul_params(arch) / M == pytest.approx(
        32 * 78.6 + 9 * 41.1 + 9 * 19.7 + 7 * 13.1 + 7 * 26.2 + 512.2, abs=2)
    assert b.prompt_matmul_params(arch) / M == pytest.approx(
        17 * 78.6 + 9 * 41.1 + 8 * 19.7 + 6.55, abs=2)
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models import hybrid
    from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig

    cfg = TransformerConfig(**dict(arch, dtype=jnp.bfloat16))
    shapes = jax.eval_shape(CausalLM(cfg).init, jax.random.PRNGKey(0))
    total = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert total / M == pytest.approx(3852.6, abs=0.5)
    assert 2 * total / 1e9 == pytest.approx(7.70, abs=0.01)
    # pools by what is WRITTEN: one whole-context layer, eight window
    # layers, 5 KiB a token a layer; the cross layers none
    assert cfg.kv_groups() == ((0, 1), (512, 8))
    assert cfg.kv_layouts(64) == ({"k": (10, 64, 128), "v": (10, 64, 128)},
                                  ) * 2
    assert b.kv_layer_token_bytes(arch) == 5120
    assert b.kv_token_bytes(arch) == 9 * 5120
    assert b.shared_kv_read_bytes(arch, 7 * 1000) == 7 * 1000 * 5120
    assert (cfg.num_linear_layers, cfg.num_attn_layers) == (9, 9)
    assert cfg.exit_at() == (1, 1) and cfg.run_feeds(cached=True) == (
        (), ("memory",), ())
    assert b.ssm_state_bytes(arch) == 16 * 5120 * 4 == 327680
    assert hybrid.state_shapes(cfg, 33) == {
        "mamba1_ssm": ((9, 33, 16, 5120), jnp.float32),
        "mamba1_conv": ((9, 33, 3, 5120), jnp.bfloat16)}
    per_seq = 9 * (b.ssm_state_bytes(arch) + b.conv_tail_bytes(arch))
    assert per_seq / M == pytest.approx(3.2, abs=0.05)
    engine = info["config"]["engine"]
    resident = (2 * total + 33 * per_seq
                + engine["kv_blocks"] * 64 * 5120 + 384 * 64 * 8 * 5120)
    assert resident / 1e9 == pytest.approx(10.5, abs=0.05)
    # one position a row attends the whole context, whatever the row's width
    assert b.qk_pairs(np.asarray([2048, 1, 0]), np.asarray([4096, 700, 9])) \
        == 6144 + 701
    # the paged calls by layer group: eight readers of the one written
    # layer, one query a row; eight window layers, every position
    assert b.paged_calls(arch) == [(0, 8, True), (512, 8, False)]
    # least work a pair: 40 scores over 64 and 40 rows 128 wide; bytes:
    # 5 KiB a key read, q in and two 128-wide rows a pair out a query
    cost = b.paged_attention_cost(arch, 3, 7000, 7000)
    assert cost["flops"] == 7000 * 40 * (2 * 64 + 2 * 128)
    assert cost["bytes"] == 7000 * 5120 + 3 * (40 * 64 + 40 * 128) * 2


# ----------------------------------------- the reference and the program

@pytest.fixture(scope="module")
def tiny():
    import jax.numpy as jnp

    from benchmark.model import seeded_params
    from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig

    arch = twin()["transformer_config"]
    model = CausalLM(TransformerConfig(**dict(arch, dtype=jnp.float32)))
    params = seeded_params(model, 3, jnp.float32)
    tokens = np.random.default_rng(4).integers(
        0, arch["vocab_size"], size=PROMPT + STEPS).tolist()
    # (the K/V rows behind the logits are the replay's: below)
    want = np.asarray(block().logits(
        params, np.asarray(tokens, np.int32), arch, 16))[:len(tokens)]
    return arch, model, params, tokens, want


def test_reference_agrees_with_the_programs_model(tiny):
    import jax
    import jax.numpy as jnp

    arch, model, params, tokens, want = tiny
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(model.apply)(
            params, jnp.asarray(tokens)[None]))[0]
    assert np.abs(got - want).max() < 2e-6 * (want.max() - want.min())
    ids = np.asarray([tokens + tokens[:1]], np.int32)
    logp = jax.nn.log_softmax(jax.jit(model.apply)(params, ids[:, :-1])[0])
    nll = -float(jnp.mean(jnp.take_along_axis(
        logp, jnp.asarray(ids[0, 1:])[:, None], -1)))
    assert float(block().loss(params, ids, arch, q_block=16)) \
        == pytest.approx(nll, rel=1e-5)


def _engine(model, params, **sizing):
    from deepspeed_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2, RaggedInferenceEngineConfig)

    return InferenceEngineV2(model, params=params,
                             config=RaggedInferenceEngineConfig(**dict(
                                 twin()["engine"], compile_ahead=0, **sizing)))


def _worst(got, want):
    return np.abs(got - want[PROMPT - 1:PROMPT + STEPS]).max() \
        / (want.max() - want.min())


def test_chunks_then_decode_through_the_pools_and_the_slots(tiny):
    arch, model, params, tokens, want = tiny
    engine = _engine(model, params)
    for at in range(0, PROMPT, 32):
        out = engine.put([7], [tokens[at:min(at + 32, PROMPT)]])
    got = [np.asarray(out[0])]
    for t in tokens[PROMPT:]:
        got.append(np.asarray(engine.put([7], [[t]])[0]))
    assert _worst(np.stack(got), want) < 5e-6 < 1e-4
    totals = engine.put_totals
    assert totals["xdec_rows"] == 3 + STEPS
    # two cross layers walk the whole context of every forward's row
    assert totals["shared_kv_read_tokens"] == 2 * (
        32 + 64 + 90 + sum(range(91, 91 + STEPS)))
    assert totals["kv_blocks_released"] > 0
    engine.flush(7)
    sm = engine.state_manager
    assert sm.allocator.free_blocks == sm.allocator.total_blocks
    assert sm.free_state_slots == sm.state_slots


def _changed(tiny, params, **change):
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig

    arch, _, _, tokens, _ = tiny
    other = CausalLM(TransformerConfig(**dict(arch, dtype=jnp.float32,
                                              **change)))
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(other.apply)(
            params, jnp.asarray(tokens)[None]))[0, PROMPT - 1:]


@pytest.mark.parametrize("change", [
    {"norm_eps": 1e-2}, {"attn_scale": 1.0}, {"sliding_window": 8}],
    ids=lambda c: next(iter(c)))
def test_a_switch_thrown_the_other_way_fails(tiny, change):
    _, _, params, _, want = tiny
    assert _worst(_changed(tiny, params, **change), want) > 1e-4


@pytest.mark.parametrize("leaf", [
    "mamba1_D", "mamba1_conv_b", "mamba1_dt_b", "wo_b", "wq_b",
    "attn_norm_b", "lambda_q1", "gmu_w_in"])
def test_a_leaf_left_out_fails(tiny, leaf):
    """What a look-alike of a layer lacks — the skip, the conv's or the
    step's bias, an attention bias, a LayerNorm's bias, λ's vectors, the
    memory's gate — set to zero in the program's weights and not in the
    reference's."""
    import jax.numpy as jnp

    _, _, params, _, want = tiny
    params = dict(params, layers={
        k: {n: jnp.zeros_like(a) if n == leaf else a for n, a in v.items()}
        for k, v in params["layers"].items()})
    assert _worst(_changed(tiny, params), want) > 1e-5


# ------------------------------------------------------ scopes and readers

def test_the_new_scope_names_resolve_through_the_blocks_scopes():
    b = block()
    body = "jit(_forward)/layers/xdec/while/body/closed_call/"
    cases = {
        body + "cross_attn/qkv/dot_general:": "qkv",
        body + "cross_attn/attend/paged_attention/pallas_call:": "attend",
        body + "cross_attn/attn_out/dot_general:": "attn_out",
        body + "gmu/dot_general:": "gmu",
        body + "mlp/dense_mlp/dot_general:": "dense_mlp",
        body + "add:": "xdec",
        "jit(_forward)/layers/mamba/mamba_scan/while/body/mul:": "mamba_scan",
        "jit(_forward)/layers/mamba/mamba_state_io/gather:":
            "mamba_state_io",
        "jit(_forward)/layers/full_attn/attend/pallas_call:": "attend",
        "jit(_forward)/logits/dot_general:": "logits",
    }
    for op_name, want in cases.items():
        assert scopes.scope_of(op_name, b.SCOPES) == want, op_name
    assert "xdec" in scopes.scope_path(
        body + "cross_attn/attend/pallas_call:", b.SCOPES)
    assert set(b.MAMBA_SCOPES) < set(b.SCOPES)
    assert b.ATTN_SCOPES == {"window": "window_attn", "full": "full_attn"}


def test_the_programs_forward_carries_the_scopes(tiny):
    import jax.numpy as jnp

    arch, model, params, *_ = tiny
    engine = _engine(model, params)
    sm = engine.state_manager
    text = engine.paged.forward.lower(
        engine.params, sm.forward_cache, jnp.zeros((1, 32), jnp.int32),
        jnp.zeros((1,), jnp.int32), jnp.full((1,), 32, jnp.int32),
        jnp.zeros((2, 1, 64), jnp.int32), jnp.zeros((1,), jnp.int32)
    ).compile().as_text()
    for name in block().SCOPES:
        assert f"/{name}/" in text or f"/{name}\"" in text, name


class _Ctx:
    def __init__(self, records, traced=True):
        _, info = real()
        self.info = info
        self.device = {"kind": "TPU v5 lite"}
        self.result = {
            "arch": info["config"]["transformer_config"],
            "window": (0.0, 100.0), "trace_marks": (10.0, 100.0),
            "program_spans": [{"name": name, "t_start": 5.0 + 10 * i,
                               "attrs": r} for i, r in enumerate(records)
                              for name in ("forward", "dispatch")]}
        self.trace = {} if traced else None


def test_the_new_readers_on_hand_made_contexts(monkeypatch):
    chunk = lambda n, rows=1: {"bucket_chunk": 2048, "valid_tokens": n,  # noqa
                               "xdec_rows": rows,
                               "shared_kv_read_tokens": 7 * n}
    step = lambda rows, ctx_: {"bucket_chunk": 1, "valid_tokens": rows,  # noqa
                               "xdec_rows": rows,
                               "shared_kv_read_tokens": 7 * rows * ctx_}
    # the first forward began before the marks: not read
    ctx = _Ctx([chunk(9), chunk(2048), step(20, 7000), chunk(1000),
                step(24, 7000), step(28, 7000)])
    assert xdec_readers.xdec_rows_share(ctx) == pytest.approx(
        100.0 * 2 / 3048)
    # a program whose tail runs on every position reads 100
    assert xdec_readers.xdec_rows_share(
        _Ctx([chunk(9), chunk(2048, 2048)])) == 100.0
    # one execution a kind of forward, as (scope path, self seconds)
    runs = {True: [[(("layers", "mamba", "mamba_scan"), 0.030),
                    (("layers", "xdec", "cross_attn", "attend"), 0.001),
                    (("layers", "xdec", "gmu"), 0.001),
                    (("logits",), 0.002)]],
            False: [[(("layers", "xdec", "cross_attn", "attend"), 0.008),
                     (("layers", "xdec", "cross_attn", "qkv"), 0.001),
                     (("layers", "full_attn", "attend"), 0.001),
                     (("layers", "xdec", "gmu"), 0.002),
                     (("logits",), 0.004)]]}
    monkeypatch.setattr(xdec_readers, "_forward_executions",
                        lambda ctx, mixed: runs[mixed])
    assert xdec_readers.path_share(ctx, "xdec", mixed=True) \
        == pytest.approx(100 * 0.002 / 0.034)
    assert xdec_readers.path_share(ctx, "cross_attn") == pytest.approx(
        100 * 0.010 / 0.050)
    assert xdec_readers.path_share(ctx, "gmu") == pytest.approx(
        100 * 0.003 / 0.050)
    assert xdec_readers.path_share(ctx, "router") is None
    # 24 rows of 7,000 the median forward: seven walks, 5 KiB a position,
    # in 8 ms under the cross layers' attend
    assert xdec_readers.ms_per_forward(ctx, "cross_attn", "attend", False) \
        == pytest.approx(8.0)
    assert xdec_readers.shared_kv_read_gbps(ctx) == pytest.approx(
        7 * 24 * 7000 * 5120 / 0.008 / 1e9)
    assert 100 < xdec_readers.shared_kv_read_gbps(ctx) < 819
    # the kernel's roofline: 24 one-token rows of 7,000 — eight reads of
    # the whole context a row, eight of the window's 512 — in 12 ms
    groups = lambda rows, valid, read0, read1, pairs1: {   # noqa: E731
        "rows": rows, "valid_tokens": valid, "kv_g0_total": 8,
        "kv_g0_window": 0, "kv_g1_window": 512, "kv_g0_read_tokens": read0,
        "kv_g0_qk_pairs": read0, "kv_g1_read_tokens": read1,
        "kv_g1_qk_pairs": pairs1}
    roof = _Ctx([groups(1, 9, 9, 9, 45),
                 groups(24, 24, 24 * 7000, 24 * 512, 24 * 512),
                 groups(1, 2048, 6144, 2559, 2048 * 512)])
    roof.trace = {"kernel_seconds": {"kernel:paged_attention": 0.012,
                                     "kernel:gmm": 1.0}}
    one_step = 8 * (24 * 7000 * 5120 + 24 * 15360) / 819e9 \
        + 8 * (24 * 512 * 5120 + 24 * 15360) / 819e9
    one_chunk = 8 * (6144 * 5120 + 15360) / 819e9 \
        + 8 * 2048 * 512 * 15360 / 197e12    # the window layers: FLOP-bound
    assert 2559 * 5120 + 2048 * 15360 < 2048 * 512 * 15360 * 819e9 / 197e12
    assert xdec_readers.paged_attention_roofline(roof) == pytest.approx(
        100 * (one_step + one_chunk) / 0.012, rel=1e-3)
    assert 50 < xdec_readers.paged_attention_roofline(roof) < 100
    # a program whose groups are not the block's: nothing is read
    roof.result["program_spans"][2]["attrs"]["kv_g1_window"] = 256
    assert xdec_readers.paged_attention_roofline(roof) is None
    # nothing to read: an untraced run, the parent's spans
    monkeypatch.undo()
    untraced = _Ctx([chunk(9), chunk(2048)], traced=False)
    parent = {"bucket_chunk": 2048, "valid_tokens": 2048}
    assert xdec_readers.xdec_rows_share(_Ctx([parent, parent])) is None
    for name in NEW_READERS:
        module = mf.find_module(mf.HERE, "layer_metrics", name)
        if name != "xdec_rows_share":   # a counter: read without a trace
            assert module.reduce(untraced) is None, name
    assert xdec_readers.shared_kv_read_gbps(_Ctx([parent] * 3, traced=False)) \
        is None


# --------------------------------------------------------------- controls

def test_the_replay_reads_the_whole_context_pool_through_the_table(tiny):
    """The replay's view: the logits rows of a causal replay and, behind
    them, layer 7's K and V of every ``KV_STRIDE``-th position as the pool
    holds them under the sequence's block table; the reference's answer
    carries the same rows."""
    arch, model, params, tokens, want = tiny
    b = block()
    engine = _engine(model, params)
    (seen, rows, got), = b.replay(engine, 9, tokens[:PROMPT], STEPS)
    engine.flush(9)
    n_kv = -(-(PROMPT + STEPS) // b.KV_STRIDE)
    assert rows[:STEPS + 1] == list(range(PROMPT - 1, PROMPT + STEPS))
    assert rows[STEPS + 1:] == [-1 - j for j in range(n_kv)]
    full = np.asarray(b.logits(params, np.asarray(seen, np.int32), arch, 16))
    assert full.shape[0] == len(seen) + n_kv
    for row, g in zip(rows, got):
        assert np.abs(g - full[row]).max() \
            < 1e-5 * (full[row].max() - full[row].min())
    kv = full[-1]       # position 0: K then V, 4 heads of 8 each, then zeros
    assert np.abs(kv[:64]).min() > 0 and not kv[64:].any()


def test_each_planted_fault_fails_the_cells_own_check(checkout):  # noqa: F811
    """``python3 -m benchmark.controls`` at the twin's size: the engine as
    served passes; a lost block of the whole-context group (caught by the
    pool's rows), a lost block of the window group and weights through
    fp8 each fail."""
    from benchmark import controls

    info = mf.resolve(mf.load(checkout), CELL, checkout)
    prompt = np.random.default_rng(8).integers(0, 256, size=PROMPT).tolist()
    seen = {name: (expected, record["ok"], record.get("max_rel_err"))
            for name, expected, record in controls.run(info, 8, prompt)}
    assert list(seen) == ["served", "lost_block_g0", "lost_block_g1",
                          "fp8_weights"]
    assert all(expected == ok for expected, ok, _ in seen.values()), seen
    assert seen["lost_block_g0"][2] > 0.1


def test_each_read_side_fault_fails_at_the_twins_size(checkout):  # noqa: F811
    """``python3 -m benchmark.xdec_controls`` at the twin's size, where the
    check's limits are float32's: the cross layers' walks cut to half their
    context and their ``λ_init`` taken at the depth within the run each
    fail, the registry's entry is as it was afterwards."""
    from benchmark import xdec_controls
    from deepspeed_tpu.models.mixers import KINDS, cross

    info = mf.resolve(mf.load(checkout), CELL, checkout)
    prompt = np.random.default_rng(8).integers(0, 256, size=PROMPT).tolist()
    seen = {name: (expected, record["ok"], record.get("max_rel_err"))
            for name, expected, record in xdec_controls.run(info, 8, prompt)}
    assert list(seen) == ["served", "short_walk", "run_depth"]
    assert all(expected == ok for expected, ok, _ in seen.values()), seen
    assert min(seen["short_walk"][2], seen["run_depth"][2]) > 0.01
    assert KINDS["cross"].paged is cross.paged


# ----------------------------------------------------------- the rehearsal

@pytest.mark.parametrize("traced", [1])
def test_the_cell_rehearsed_on_the_cpu(checkout, capsys, traced):  # noqa: F811
    path = os.path.join(checkout, "benchmark/workloads", CELL + ".json")
    _write(path, dict(_read(path), rate_rps=8.0, trace_s=1.0))
    manifest = mf.load(checkout)
    mf.validate(manifest, checkout)
    info = mf.resolve(manifest, CELL, checkout)
    assert info["config"] == twin()
    assert info["traffic"] == _read(os.path.join(TWINS, "traffic",
                                                 "deepthink.json"))
    line, extra = rehearse(checkout, capsys, CELL, traced)
    assert line["correct"], extra["why_not"]
    assert line["failed"] == 0 and line["attempted"] > 5
    check_line(line, manifest, CELL, "per_layer")
    check = extra["counters"]["logits_check"]
    assert 0 < check["max_rel_err"] < 1e-4 and check["rms_rel_err"] < 1e-4
    assert extra["counters"]["state_slots_held"] == 0
    # off the chip the counters and the spans are read, the device is not:
    # the exit's counter says one position a row of the chunk forwards
    metrics = line["metrics"]
    assert {"batch_seqs_mean", "pad_ratio", "xdec_rows_share",
            "state_slots_peak_share", "kv_resident_ratio"} <= set(metrics), \
        sorted(metrics)
    assert 0 < metrics["xdec_rows_share"]["value"] < 100.0 / 12
    assert not {"cross_attn_share", "gmu_share", "xdec_prefill_share",
                "shared_kv_read_gbps", "mamba_share"} & set(metrics)
