"""The ``qwen3_next`` block as the benchmark finds it: the manifest with
its entries, the reference against the program's model at a tiny size,
the arithmetic against hand counts, the configuration against the catalog
row it was drawn from, the scope names, the new readers on hand-made
contexts, and the cell rehearsed end to end on the CPU."""

import json
import os

import numpy as np
import pytest
from qwen3_next_tiny import TINY_QWEN3_NEXT
from test_benchmark_runners import (_read, _write, check_line,  # noqa: F401
                                    checkout, rehearse)

from benchmark import hybrid_readers, peaks, scopes, trace
from benchmark import manifest as mf
from benchmark.model import check_consistent
from benchmark.probe import Probe
from benchmark.run import Context

CELL, CONFIG = "qwen3-next-80b-a3b.longdoc", "qwen3-next-80b-a3b"
NEW_READERS = ("gdn_share", "gdn_scan_prefill_ms", "experts_share",
               "moe_route_share", "moe_rows_per_expert",
               "state_slots_peak_share", "paged_attn_hybrid_roofline",
               "gmm_roofline")
#: the catalog row's ``config`` (model-configs guide, architectures.jsonl,
#: ``Qwen3-Next-80B-A3B-Instruct``), as the issue drew it
CATALOG = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
    "num_hidden_layers": 48, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
CATALOG_FILE = "/opt/skills/guides/model-configs/architectures.jsonl"


def block():
    return mf.find_module(mf.HERE, "blocks", "qwen3_next")


def real():
    manifest = mf.load()
    return manifest, mf.resolve(manifest, CELL)


def test_the_manifest_validates_with_the_new_entries():
    manifest, info = real()
    mf.validate(manifest)
    assert info["block"].__name__.endswith("qwen3_next")
    assert info["traffic"]["loop"] == "open"
    assert info["cell"]["chips"] == 1
    mine = {m["name"] for m in mf.metrics_for(manifest, "per_layer", CELL)}
    assert set(NEW_READERS) <= mine and "paged_attn_roofline" not in mine
    doc = {m["name"] for m in mf.metrics_for(manifest, "per_layer",
                                             "pythia-1.4b.doc")}
    assert doc - {"paged_attn_roofline"} <= mine
    assert {m["name"] for m in mf.metrics_for(manifest, "end_to_end", CELL)} \
        == {"ttft_p90_ms", "tpot_p90_ms", "setup_s"}


def test_the_configuration_is_the_catalog_rows_but_for_what_it_reduces():
    manifest, info = real()
    config, entry = info["config"], info["config_entry"]
    assert sorted(entry["reduced"]) == ["num_experts", "num_hidden_layers",
                                        "vocab_size"]
    assert sorted(config["reduced"]) == sorted(entry["reduced"])
    for key, value in CATALOG.items():
        if key in entry["reduced"]:
            assert config["reduced"][key] == [value, config[key]], key
        else:
            assert config[key] == value, key
    check_consistent(config, info["block"])
    arch = config["transformer_config"]
    # the router is as wide as published; the share held is the reduced key
    assert arch["moe_num_experts"] == CATALOG["num_experts"]
    assert arch["moe_held_experts"] == [0, config["num_experts"]]
    assert arch["layer_pattern"] == ["linear"] * 3 + ["full"]
    assert arch["max_seq_len"] >= 33280
    if os.path.isfile(CATALOG_FILE):        # the row itself, where it is
        with open(CATALOG_FILE) as f:
            rows = [json.loads(line) for line in f if line.strip()]
        row = next(r for r in rows if r["source_url"] == entry["source"])
        assert row["config"] == CATALOG


@pytest.mark.parametrize("wrong", [{"head_dim": 128},
                                   {"num_experts_per_tok": 8},
                                   {"linear_num_value_heads": 16},
                                   {"shared_expert_intermediate_size": 0}])
def test_a_published_key_that_disagrees_with_the_program_is_refused(wrong):
    _, info = real()
    with pytest.raises(ValueError, match=next(iter(wrong))):
        check_consistent(dict(info["config"], **wrong), info["block"])


def test_matmul_params_against_hand_counts():
    _, info = real()
    arch, b = info["config"]["transformer_config"], info["block"]
    # a DeltaNet mixer: 2048 -> 2048 + 2048 + 4096 + 4096, 2048 -> 64,
    # 4096 -> 2048
    assert b.deltanet_matmul_params(arch) == 2048 * 12288 + 2048 * 64 \
        + 4096 * 2048 == 33_685_504
    # an attention mixer: q twice as wide, k, v over 2 heads, o
    assert b.attention_matmul_params(arch) == 2048 * 8192 + 2 * 2048 * 512 \
        + 4096 * 2048 == 27_262_976
    assert b.expert_matmul_params(arch) == 3 * 2048 * 512 == 3_145_728
    ffn = 2048 * 512 + (3 * 2048 * 512 + 2048) + 10 * 0.5 * 3_145_728
    assert b.matmul_params(arch) == pytest.approx(
        3 * 33_685_504 + 27_262_976 + 4 * ffn + 2048 * 75968)
    assert b.attention_layers(arch) == 1
    # what forward_flops makes of it: 2 a weight a token
    assert peaks.forward_flops(b, arch, 1, 0) == 2.0 * b.matmul_params(arch)


def test_kernel_costs_at_the_stated_head_size():
    _, info = real()
    arch, b = info["config"]["transformer_config"], info["block"]
    cost = b.paged_attention_cost(arch, 32, 32 * 16384, 32 * 16384)
    assert cost["flops"] == 4.0 * 16 * 256 * 32 * 16384
    assert cost["bytes"] == 2.0 * 2 * 256 * 2 * 32 * 16384 \
        + 2.0 * 16 * 256 * 2 * 32
    # peaks.py would take the head size for hidden/heads = 128
    assert cost["flops"] == 2 * peaks.paged_attention_cost(
        arch, 32, 32 * 16384, 32 * 16384)["flops"]
    # a decode step of 32 tokens: 160 held pairs a layer, and nearly
    # (1 - (1 - 10/512)^32) of the 256 experts hit
    one = b.gmm_cost(arch, 32)
    hit = 256 * (1 - (1 - 10 / 512) ** 32)
    assert 110 < hit < 125
    assert one["flops"] == pytest.approx(4 * 2 * 3_145_728 * 160)
    assert one["bytes"] == pytest.approx(
        4 * 2 * (hit * 3_145_728 + 160 * (3 * 2048 + 3 * 512)))


def tiny_model(**overrides):
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig

    arch = dict(TINY_QWEN3_NEXT["transformer_config"], **overrides)
    cfg = TransformerConfig(**dict(arch, dtype=jnp.float32))
    model = CausalLM(cfg)
    from benchmark.model import seeded_params

    return arch, model, seeded_params(model, 3, jnp.float32)


@pytest.mark.parametrize("overrides", [
    {}, {"moe_held_experts": None, "moe_norm_topk": False},
    {"layer_pattern": ["linear", "full"], "num_layers": 4,
     "moe_shared_intermediate_size": 0},
    {"layer_pattern": ["full"], "num_layers": 2},
], ids=["published-shape", "all-held-no-renorm", "period-of-two-no-shared",
        "attention-only"])
def test_reference_agrees_with_the_programs_model(overrides):
    import jax
    import jax.numpy as jnp

    arch, model, params = tiny_model(**overrides)
    b = block()
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0,
                                arch["vocab_size"])
    got, aux = jax.jit(lambda p, t: model.apply(p, t, return_aux=True))(
        params, tokens)
    ref = jax.jit(lambda p, t: b.logits(p, t, arch, q_block=16))
    for row in range(2):
        want = np.asarray(ref(params, tokens[row]))
        assert np.abs(np.asarray(got[row]) - want).max() \
            < 1e-4 * np.abs(want).max()
    # the loss over the same tokens (the program's adds its routing aux)
    ids = jnp.concatenate([tokens, tokens[:, :1]], axis=1)
    program = float(jax.jit(model.loss)(params, {"input_ids": ids[:, :-1],
                                                 "labels": ids[:, 1:]}))
    assert float(jax.jit(lambda p, i: b.loss(p, i, arch, q_block=16))(
        params, ids)) == pytest.approx(
            program - model.cfg.moe_aux_loss_coef * float(aux), rel=1e-5)


def test_the_new_scope_names_resolve_through_the_blocks_scopes():
    b = block()
    body = "jit(_forward)/layers/while/body/closed_call/"
    cases = {
        body + "linear_attn/gdn_scan/while/body/dot_general:": "gdn_scan",
        body + "linear_attn/gdn_proj/dot_general:": "gdn_proj",
        body + "linear_attn/gdn_conv/mul:": "gdn_conv",
        body + "linear_attn/gdn_out/dot_general:": "gdn_out",
        body + "linear_attn/scatter:": "linear_attn",
        body + "mlp/router/dot_general:": "router",
        body + "mlp/experts/ragged_dot:": "experts",
        body + "mlp/shared_expert/dot_general:": "shared_expert",
        body + "mlp/add:": "mlp",
        body + "attend/paged_attention/pallas_call:": "attend",
        body + "dynamic_slice:": scopes.SCAN_OVERHEAD,
    }
    for op_name, want in cases.items():
        assert scopes.scope_of(op_name, b.SCOPES) == want, op_name
    # without the block's names the FFN is one scope and the mixer is the
    # scan's own time
    assert scopes.scope_of(body + "mlp/experts/ragged_dot:") == "mlp"
    assert scopes.scope_of(body + "linear_attn/gdn_scan/dot:") \
        == scopes.SCAN_OVERHEAD
    assert set(b.GDN_SCOPES) < set(b.SCOPES)


def _ev(line, name, start, dur, plane="/device:TPU:0", **extra):
    return dict(plane=plane, line=line, name=name, start=start, dur=dur,
                **extra)


def hand_made_context(monkeypatch):
    """10 s window, two executions of the forward. A mixed step 1..5
    ([2x64]): gdn_proj 1..2, gdn_scan 2..3.5, the paged kernel 3.5..4,
    experts 4..5 (its gmm kernel 4.4..5). A decode step 6..8 ([2x1]):
    gdn_scan 6..6.5, router 6.5..7, the gmm kernel 7..8."""
    _, info = real()
    body = "jit(_forward)/layers/while/body/closed_call/"
    call = " custom-call(bf16[8]{0} %q), custom_call_target=\"tpu_custom_call\""
    op = lambda n, a, d, scope: _ev(                            # noqa: E731
        trace.OPS_LINE, f"%fusion.{n} = bf16[8]{{0}} fusion(%a)", a, d,
        op_name=body + scope + "/dot_general:")
    events = [
        _ev("python3", trace.WINDOW, 0.0, 10.0, plane="/host:CPU"),
        _ev("python3", "bench:forward[2x64]", 0.9, 0.2, plane="/host:CPU"),
        _ev("python3", "bench:forward[2x1]", 5.9, 0.2, plane="/host:CPU"),
        _ev(trace.MODULES_LINE, "jit__forward(1)", 1.0, 4.0),
        _ev(trace.MODULES_LINE, "jit__forward(2)", 6.0, 2.0),
        op(1, 1.0, 1.0, "linear_attn/gdn_proj"),
        op(2, 2.0, 1.5, "linear_attn/gdn_scan"),
        _ev(trace.OPS_LINE, "%paged_attention.1 = bf16[8]{0}" + call, 3.5,
            0.5, op_name=body + "attend/paged_attention/pallas_call:"),
        op(3, 4.0, 0.4, "mlp/experts"),
        _ev(trace.OPS_LINE, "%gmm.3 = bf16[8]{0}" + call, 4.4, 0.6,
            op_name=body + "mlp/experts/jit(gmm)/pallas_call:"),
        op(4, 6.0, 0.5, "linear_attn/gdn_scan"),
        op(5, 6.5, 0.5, "mlp/router"),
        _ev(trace.OPS_LINE, "%gmm.3 = bf16[8]{0}" + call, 7.0, 1.0,
            op_name=body + "mlp/experts/jit(gmm)/pallas_call:"),
    ]
    probe = Probe()
    probe.spans += [
        ("forward", 1.0, 1.1, {"valid_tokens": 100, "kv_read_tokens": 4000,
                               "qk_pairs": 300000}),
        ("forward", 6.0, 6.1, {"valid_tokens": 2, "kv_read_tokens": 4100,
                               "qk_pairs": 4100})]
    spans = [{"name": "forward", "t_start": t, "t_end": t + 0.1,
              "attrs": {"n_seqs": 2, "state_slots_used": used,
                        "moe_rows_held": held}}
             for t, used, held in ((1.0, 8, 4 * 5 * 100), (6.0, 24, 4 * 5 * 2))]
    result = {"xplane": "hand-made", "chips": 1, "window": (0.0, 10.0),
              "trace_marks": (0.0, 10.0), "probe": probe,
              "program_spans": spans,
              "arch": info["config"]["transformer_config"]}
    ctx = Context(result, info, {"platform": "tpu", "kind": "TPU v5 lite",
                                 "count": 1})
    ctx._trace = trace.summarize(events, chips=1)
    ctx._scopes = scopes.summarize(events, chips=1,
                                   block_scopes=info["block"].SCOPES)
    monkeypatch.setattr(scopes, "load", lambda path: events)
    return ctx, info


def test_each_new_reader_reads_a_hand_made_trace(monkeypatch):
    ctx, info = hand_made_context(monkeypatch)
    arch, b = info["config"]["transformer_config"], info["block"]
    read = lambda name: mf.find_module(mf.HERE, "layer_metrics",  # noqa: E731
                                       name).reduce(ctx)
    busy = 6.0
    assert read("gdn_share") == pytest.approx(100 * 3.0 / busy)
    assert read("experts_share") == pytest.approx(100 * 2.0 / busy)
    assert read("moe_route_share") == pytest.approx(100 * 0.5 / busy)
    # the mixed step's 1.5 s, not the decode step's 0.5 s
    assert read("gdn_scan_prefill_ms") == pytest.approx(1500.0)
    assert hybrid_readers.scope_ms_per_forward(ctx, "gdn_scan", mixed=False) \
        == pytest.approx(500.0)
    assert read("moe_rows_per_expert") == pytest.approx(
        (4 * 5 * 100 + 4 * 5 * 2) / 2 / (4 * 256))
    assert read("state_slots_peak_share") == pytest.approx(100 * 24 / 32)
    least = sum(peaks.roofline_seconds(b.paged_attention_cost(arch, *a),
                                       "TPU v5 lite")
                for a in ((100, 4000, 300000), (2, 4100, 4100)))
    assert read("paged_attn_hybrid_roofline") == pytest.approx(
        100 * least / 0.5)
    assert read("gmm_roofline") == pytest.approx(
        100 * sum(peaks.roofline_seconds(b.gmm_cost(arch, t), "TPU v5 lite")
                  for t in (100, 2)) / 1.6)
    assert dict(ctx.trace["device_ops"])["kernel:gmm"] == pytest.approx(1.6)
    for name in NEW_READERS:
        assert 0 < read(name) < 1e6, name


def test_each_new_reader_returns_none_where_there_is_nothing_to_read(
        monkeypatch):
    # no trace at all: an untraced run, or a rehearsal off the chip
    _, info = real()
    result = {"xplane": "/nonexistent.xplane.pb", "chips": 1,
              "window": (0.0, 10.0), "program_spans": [],
              "arch": info["config"]["transformer_config"]}
    ctx = Context(result, info, {"platform": "cpu", "kind": "cpu",
                                 "count": 1})
    for name in NEW_READERS:
        assert mf.find_module(mf.HERE, "layer_metrics", name).reduce(ctx) \
            is None, name
    # a trace of a program without the names (the parent's), read under a
    # block without the scopes or the cost functions: nothing, no error
    ctx, info = hand_made_context(monkeypatch)
    bare = [dict(e, op_name="") if "op_name" in e else e
            for e in scopes.load("")]
    monkeypatch.setattr(scopes, "load", lambda path: bare)
    ctx._scopes = scopes.summarize(bare, chips=1)
    ctx.result["program_spans"] = [
        {"name": "forward", "t_start": 1.0, "t_end": 1.1,
         "attrs": {"n_seqs": 2}}]
    ctx.info = dict(info, block=mf.find_module(mf.HERE, "blocks", "dense"))
    for name in NEW_READERS:
        assert mf.find_module(mf.HERE, "layer_metrics", name).reduce(ctx) \
            is None, name


@pytest.mark.parametrize("traced", [0, 1])
def test_the_cell_rehearsed_on_the_cpu(checkout, capsys, traced):  # noqa: F811
    """The whole runner over the hybrid engine at the tiny twin's size:
    requests in chunks through both caches, the logits check against this
    block's reference, every KV block and every state slot back."""
    path = os.path.join(checkout, "benchmark/workloads", CELL + ".json")
    _write(path, dict(_read(path), rate_rps=10.0, trace_s=1.0))
    manifest = mf.load(checkout)
    mf.validate(manifest, checkout)
    group = "per_layer" if traced else "end_to_end"
    line, extra = rehearse(checkout, capsys, CELL, traced)
    assert line["correct"], extra["why_not"]
    assert line["failed"] == 0 and line["attempted"] > 5
    check_line(line, manifest, CELL, group)
    check = extra["counters"]["logits_check"]
    assert 0 < check["max_rel_err"] < 1e-4 and check["rms_rel_err"] < 1e-4
    if traced:
        # off the chip the counters are read, the device is not
        assert {"moe_rows_per_expert", "state_slots_peak_share",
                "batch_seqs_mean"} <= set(line["metrics"])
        assert "gdn_share" not in line["metrics"]
    else:
        assert set(line["metrics"]) == {"ttft_p90_ms", "tpot_p90_ms",
                                        "setup_s"}
