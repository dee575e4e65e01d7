"""The ``trinity`` block as the benchmark finds it: the manifest with its
entries, the configuration against the catalog row it was drawn from, the
reference against the program's model at the tiny twin's size (and the
eight shares against the uncut layer), the arithmetic against hand
counts, the scope names, the new readers on hand-made contexts, and the
cell rehearsed end to end on the CPU under the real names."""

import json
import os

import numpy as np
import pytest
from test_benchmark_runners import (_read, _write, check_line,  # noqa: F401
                                    checkout, rehearse)

from benchmark import hybrid_readers, kv_group_readers, peaks, scopes, trace
from benchmark import manifest as mf
from benchmark.model import check_consistent
from benchmark.probe import Probe
from benchmark.run import Context

CELL, CONFIG = "trinity-large-preview.mixedctx", "trinity-large-preview"
NEW_READERS = ("attn_window_share", "attn_full_share", "kv_resident_ratio",
               "kv_window_blocks_peak_share", "kv_full_blocks_peak_share",
               "paged_attn_window_roofline")
SHARED_READERS = ("experts_share", "moe_route_share", "moe_rows_per_expert")
CATALOG_FILE = "/opt/skills/guides/model-configs/architectures.jsonl"
TWINS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "twins")
#: the catalog row's ``config`` (model-configs guide, architectures.jsonl,
#: ``Trinity-Large-Preview``), as the issue drew it; ``layer_types`` is
#: sliding x 3 + full, fifteen times
CATALOG = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 3072, "intermediate_size": 12288,
    "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 15,
    "load_balance_coeff": 5e-05, "max_position_embeddings": 262144,
    "model_type": "afmoe", "moe_intermediate_size": 3072,
    "mup_enabled": True, "n_group": 1, "num_attention_heads": 48,
    "num_dense_layers": 6, "num_expert_groups": 1, "num_experts": 256,
    "num_experts_per_tok": 4, "num_hidden_layers": 60,
    "num_key_value_heads": 8, "num_limited_groups": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "route_norm": True, "route_scale": 2.448,
    "score_func": "sigmoid", "sliding_window": 4096,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    "vocab_size": 200192}


def block():
    return mf.find_module(mf.HERE, "blocks", "trinity")


def real():
    manifest = mf.load()
    return manifest, mf.resolve(manifest, CELL)


def twin():
    return _read(os.path.join(TWINS, "configs", CONFIG + ".json"))


def test_the_manifest_validates_with_the_new_entries():
    manifest, info = real()
    mf.validate(manifest)
    # (no pin on the totals: a later PR appends, and may not edit this file)
    assert len(manifest["workloads"]) >= 6
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) \
        <= max(1, len(manifest["workloads"]) // 4)
    assert info["block"].__name__.endswith("trinity")
    assert info["traffic"]["loop"] == "open"
    assert info["traffic"]["generator"] == "stratified"
    assert info["cell"]["chips"] == 1
    mine = {m["name"] for m in mf.metrics_for(manifest, "per_layer", CELL)}
    assert set(NEW_READERS) | set(SHARED_READERS) <= mine
    # the dense roofline (head size hidden / heads), what only a recurrent
    # block has, and the grouped matmul's roofline are not this cell's:
    # its cost function counts the experts a forward is *expected* to hit
    # under even, independent routing, and this model's tokens route alike
    # (the chip read 110%: PERF.md section 7)
    assert not mine & {"paged_attn_roofline", "paged_attn_hybrid_roofline",
                       "gdn_share", "gdn_scan_prefill_ms",
                       "state_slots_peak_share", "gmm_roofline"}
    doc = {m["name"] for m in mf.metrics_for(manifest, "per_layer",
                                             "pythia-1.4b.doc")}
    assert doc - {"paged_attn_roofline"} <= mine
    assert {m["name"] for m in mf.metrics_for(manifest, "end_to_end", CELL)} \
        == {"ttft_p90_ms", "tpot_p90_ms", "setup_s"}
    # listed for this cell first; a later cell that reads the same names
    # is appended behind it
    for m in manifest["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"][0] == CELL


def test_the_configuration_is_the_catalog_rows_but_for_what_it_reduces():
    manifest, info = real()
    config, entry = info["config"], info["config_entry"]
    assert entry["reduced"] == ["num_hidden_layers", "num_dense_layers",
                                "layer_types", "num_experts", "vocab_size"]
    assert sorted(config["reduced"]) == sorted(entry["reduced"])
    for key, value in CATALOG.items():
        if key in entry["reduced"]:
            assert config["reduced"][key] == [value, config[key]], key
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    check_consistent(config, info["block"])
    arch = config["transformer_config"]
    # the router is as wide as published; the share held is the reduced key
    assert arch["moe_num_experts"] == CATALOG["num_experts"]
    assert arch["moe_held_experts"] == [0, config["num_experts"]]
    assert arch["moe_top_k"] == 4 and arch["moe_route_scale"] == 2.448
    # layer 0 (dense, sliding) then one whole period
    kinds = {"sliding_attention": "window", "full_attention": "full"}
    assert [kinds[t] for t in config["layer_types"]] \
        == arch["lead_layers"] + arch["layer_pattern"]
    assert len(arch["lead_layers"]) == config["num_dense_layers"] == 1
    assert arch["num_layers"] == config["num_hidden_layers"] == 5
    assert arch["embed_scale"] == pytest.approx(3072 ** 0.5)
    assert arch["rope_kinds"] == ["window"] and arch["rope_pct"] == 1.0
    assert arch["max_seq_len"] == 65536 + 1536
    for key in ("assumed", "deployment", "published", "reduced", "engine",
                "check"):
        assert config[key], key
    # the twin has the file's shape: the same block, the same switches
    small = twin()
    assert small["block"] == config["block"]
    assert set(small["transformer_config"]) == set(arch)
    if os.path.isfile(CATALOG_FILE):        # the row itself, where it is
        with open(CATALOG_FILE) as f:
            rows = [json.loads(line) for line in f if line.strip()]
        row = next(r for r in rows if r["source_url"] == entry["source"])
        assert row["config"] == CATALOG


@pytest.mark.parametrize("wrong", [{"head_dim": 64},
                                   {"num_experts_per_tok": 8},
                                   {"route_scale": 1.0},
                                   {"score_func": "softmax"},
                                   {"sliding_window": 2048},
                                   {"moe_intermediate_size": 1024}])
def test_a_published_key_that_disagrees_with_the_program_is_refused(wrong):
    _, info = real()
    with pytest.raises(ValueError, match=next(iter(wrong))):
        check_consistent(dict(info["config"], **wrong), info["block"])


def test_matmul_params_against_hand_counts():
    _, info = real()
    arch, b = info["config"]["transformer_config"], info["block"]
    # q, gate, o: 3072 x 6144 each; k, v: 3072 x 1024 each
    assert b.attention_matmul_params(arch) == 3 * 3072 * 6144 \
        + 2 * 3072 * 1024 == 62_914_560
    assert b.expert_matmul_params(arch) == 3 * 3072 * 3072 == 28_311_552
    assert b.layer_kinds(arch) == {"window": 4, "full": 1, "lead": 1,
                                   "sparse": 4}
    sparse = 3072 * 256 + 28_311_552 + 4 * (32 / 256) * 28_311_552
    assert b.matmul_params(arch) == pytest.approx(
        5 * 62_914_560 + 3 * 3072 * 12288 + 4 * sparse + 3072 * 25024)
    assert peaks.forward_flops(b, arch, 1, 0) == 2.0 * b.matmul_params(arch)
    assert b.attention_calls(arch) == [(0, 1), (4096, 4)]


def test_kernel_costs_are_bounded_by_the_window():
    _, info = real()
    arch, b = info["config"]["transformer_config"], info["block"]
    from deepspeed_tpu.inference.v2.engine_v2 import _keys_and_pairs

    def windowed(window, rows):     # the program's count, summed over rows
        return tuple(map(sum, zip(*(_keys_and_pairs(window, seen, n)
                                    for seen, n in rows))))

    # a 1,024-token chunk from position 20,000 and a decode token at 9,000
    rows = [(20000, 1024), (9000, 1)]
    assert windowed(0, rows) == (21024 + 9001, 1024 * 20000
                                 + 1024 * 1025 // 2 + 9001)
    # under the window: the chunk reads 4,095 + 1,024 keys, every query 4,096
    assert windowed(4096, rows) == (4095 + 1024 + 4096,
                                    1024 * 4096 + 4096)
    # a chunk that crosses the window's edge: 96 rows short of a window
    assert windowed(4096, [(4000, 256)]) == (
        4256 - 0, 96 * 4000 + 96 * 97 // 2 + 160 * 4096)
    read, pairs = windowed(4096, rows)
    cost = b.paged_attention_cost(arch, 1025, read, pairs)
    assert cost["flops"] == 4.0 * 48 * 128 * pairs
    assert cost["bytes"] == 2.0 * 8 * 128 * 2 * read + 2.0 * 48 * 128 * 2 * 1025
    # peaks.py would take the head size for hidden / heads = 64
    assert cost["flops"] == 2 * peaks.paged_attention_cost(
        arch, 1025, read, pairs)["flops"]
    # a chunk of 1,024 tokens: 512 held pairs a layer, four sparse layers,
    # and nearly all 32 held experts hit
    one = b.gmm_cost(arch, 1024)
    hit = 32 * (1 - (1 - 4 / 256) ** 1024)
    assert 31.9 < hit < 32
    assert one["flops"] == pytest.approx(4 * 2 * 28_311_552 * 512)
    assert one["bytes"] == pytest.approx(
        4 * 2 * (hit * 28_311_552 + 512 * (3 * 3072 + 3 * 3072)))


def tiny_model(**overrides):
    import jax.numpy as jnp

    from benchmark.model import seeded_params
    from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig

    arch = dict(twin()["transformer_config"], **overrides)
    model = CausalLM(TransformerConfig(**dict(arch, dtype=jnp.float32)))
    return arch, model, seeded_params(model, 3, jnp.float32)


@pytest.mark.parametrize("overrides", [
    {}, {"moe_held_experts": None},
    {"lead_layers": [], "num_layers": 4, "layer_pattern": ["window", "full"]},
    {"lead_layers": ["full", "window"], "num_layers": 6, "sliding_window": 8},
], ids=["published-shape", "all-held", "no-lead-period-of-two",
        "two-lead-layers-short-window"])
def test_reference_agrees_with_the_programs_model(overrides):
    import jax
    import jax.numpy as jnp

    arch, model, params = tiny_model(**overrides)
    b = block()
    tokens = jax.random.randint(jax.random.PRNGKey(11), (2, 80), 0,
                                arch["vocab_size"])
    got, aux = jax.jit(lambda p, t: model.apply(p, t, return_aux=True))(
        params, tokens)
    # every position answered: ``logits`` leaves the ill-conditioned out
    ref = jax.jit(lambda p, t: b.tie_margins(p, t, arch, q_block=16)[0])
    for row in range(2):
        want = np.asarray(ref(params, tokens[row]))
        assert np.abs(np.asarray(got[row]) - want).max() \
            < 1e-4 * np.abs(want).max()
    if overrides:       # the loss once, at the published shape
        return
    ids = jnp.concatenate([tokens, tokens[:, :1]], axis=1)
    program = float(jax.jit(model.loss)(params, {"input_ids": ids[:, :-1],
                                                 "labels": ids[:, 1:]}))
    assert float(jax.jit(lambda p, i: b.loss(p, i, arch, q_block=16))(
        params, ids)) == pytest.approx(
            program - model.cfg.moe_aux_loss_coef * float(aux), rel=1e-5)


def test_the_selection_bias_and_the_gains_show_where_they_are_dropped():
    """What the configuration's ``assumed`` says of its weights: a bias
    dropped from the selection, or leaking into the weights, and a norm
    left out disagree with the reference."""
    import jax

    arch, model, params = tiny_model()
    b = block()
    tokens = jax.random.randint(jax.random.PRNGKey(2), (60,), 0,
                                arch["vocab_size"])
    want = np.asarray(b.tie_margins(params, tokens, arch, q_block=16)[0])
    scale = np.abs(want).max()
    apply = jax.jit(lambda p: model.apply(p, tokens[None])[0])
    zero = jax.tree_util.tree_map_with_path(
        lambda path, a: a * 0 if "router_b" in str(path[-1]) else a, params)
    assert float(np.abs(np.asarray(apply(zero)) - want).max()) > 1e-3 * scale
    ones = jax.tree_util.tree_map_with_path(
        lambda path, a: a * 0 + 1 if "post_mlp_norm_w" in str(path[-1])
        else a, params)
    assert float(np.abs(np.asarray(apply(ones)) - want).max()) > 1e-3 * scale


def test_the_eight_shares_and_the_shared_expert_once_add_up_to_the_layer():
    """The share is the model: routed over all experts, each share's
    routed part over its own experts, summed over the shares, plus the
    shared expert once, is the uncut layer's FFN — in the reference, and
    in the program's ``moe_ffn`` given each share's weights."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models import hybrid
    from deepspeed_tpu.models.transformer import TransformerConfig

    arch, model, params = tiny_model(moe_held_experts=None)
    b = block()
    lp = jax.tree.map(lambda a: a[0], params["layers"]["slot1"])
    h = jax.random.normal(jax.random.PRNGKey(4), (50, arch["hidden_size"]))
    E, n = arch["moe_num_experts"], arch["moe_num_experts"] // 8
    with jax.default_matmul_precision("highest"):
        whole = b.routed_part(h, lp, arch, held=(0, E)) + b.shared_part(h, lp)
        parts, program = [], []
        for lo in range(0, E, n):
            share = dict(lp, **{k: lp[k][lo:lo + n]
                                for k in ("w_in", "w_gate", "w_out")})
            parts.append(b.routed_part(h, share, arch, held=(lo, n)))
            cfg = TransformerConfig(**dict(arch, dtype=jnp.float32,
                                           moe_held_experts=(lo, n)))
            program.append(hybrid.moe_ffn(cfg, h[None], share)[0][0])
        shared = b.shared_part(h, lp)
    assert np.allclose(sum(parts) + shared, whole, atol=1e-5)
    # each share of the program carries the shared expert: once is kept
    assert np.allclose(sum(program) - 7 * shared, whole, atol=1e-4)
    # and no share is empty of routed work
    assert all(float(jnp.abs(p).max()) > 0 for p in parts)


def test_the_new_scope_names_resolve_through_the_blocks_scopes():
    b = block()
    body = "jit(_forward)/layers/while/body/closed_call/"
    cases = {
        body + "window_attn/attend/paged_attention/pallas_call:": "attend",
        body + "full_attn/attend/paged_attention/pallas_call:": "attend",
        body + "window_attn/qkv/dot_general:": "qkv",
        body + "full_attn/kv_write/scatter:": "kv_write",
        body + "window_attn/mul:": "window_attn",
        "jit(_forward)/layers/mlp/dense_mlp/dot_general:": "dense_mlp",
        body + "mlp/router/dot_general:": "router",
        body + "mlp/experts/jit(gmm)/pallas_call:": "experts",
        body + "mlp/shared_expert/dot_general:": "shared_expert",
        body + "mlp/add:": "mlp",
        body + "dynamic_slice:": scopes.SCAN_OVERHEAD,
    }
    for op_name, want in cases.items():
        assert scopes.scope_of(op_name, b.SCOPES) == want, op_name
    assert "window_attn" in scopes.scope_path(
        body + "window_attn/attend/paged_attention/pallas_call:", b.SCOPES)
    assert set(b.ATTN_SCOPES.values()) < set(b.SCOPES)


def _ev(line, name, start, dur, plane="/device:TPU:0", **extra):
    return dict(plane=plane, line=line, name=name, start=start, dur=dur,
                **extra)


def _record(**over):
    """A put's record as the program's ``forward`` span carries it."""
    base = {"n_seqs": 2, "valid_tokens": 100, "kv_read_tokens": 9000,
            "qk_pairs": 500000, "kv_blocks_released": 3,
            "kv_bytes_resident": 40, "kv_bytes_unreleased": 100,
            "kv_g0_window": 0, "kv_g0_in_use": 50, "kv_g0_total": 200,
            "kv_g0_read_tokens": 9000, "kv_g0_qk_pairs": 500000,
            "kv_g1_window": 4096, "kv_g1_in_use": 30, "kv_g1_total": 40,
            "kv_g1_read_tokens": 5000, "kv_g1_qk_pairs": 300000,
            "moe_rows_held": 4 * 100 * 4 * 32 // 256}
    return dict(base, **over)


def hand_made_context(monkeypatch):
    """10 s window, two executions of the forward. A mixed step 1..5
    ([1x128]): a window layer's qkv 1..1.5 and its paged kernel 1.5..3,
    the full layer's paged kernel 3..3.5, experts 3.5..5 (its gmm kernel
    4..5). A decode step 6..8 ([2x1]): a window layer's paged kernel
    6..6.5, the full layer's qkv 6.5..7, the dense MLP 7..8."""
    _, info = real()
    body = "jit(_forward)/layers/while/body/closed_call/"
    call = " custom-call(bf16[8]{0} %q), custom_call_target=\"tpu_custom_call\""
    op = lambda n, a, d, scope: _ev(                            # noqa: E731
        trace.OPS_LINE, f"%fusion.{n} = bf16[8]{{0}} fusion(%a)", a, d,
        op_name=body + scope + "/dot_general:")
    kernel = lambda name, a, d, scope: _ev(                     # noqa: E731
        trace.OPS_LINE, f"%{name}.1 = bf16[8]{{0}}" + call, a, d,
        op_name=body + scope + "/pallas_call:")
    events = [
        _ev("python3", trace.WINDOW, 0.0, 10.0, plane="/host:CPU"),
        _ev("python3", "bench:forward[1x128]", 0.9, 0.2, plane="/host:CPU"),
        _ev("python3", "bench:forward[2x1]", 5.9, 0.2, plane="/host:CPU"),
        _ev(trace.MODULES_LINE, "jit__forward(1)", 1.0, 4.0),
        _ev(trace.MODULES_LINE, "jit__forward(2)", 6.0, 2.0),
        op(1, 1.0, 0.5, "window_attn/qkv"),
        kernel("paged_attention", 1.5, 1.5,
               "window_attn/attend/paged_attention"),
        kernel("paged_attention", 3.0, 0.5, "full_attn/attend/paged_attention"),
        op(2, 3.5, 0.5, "mlp/experts"),
        kernel("gmm", 4.0, 1.0, "mlp/experts/jit(gmm)"),
        kernel("paged_attention", 6.0, 0.5,
               "window_attn/attend/paged_attention"),
        op(3, 6.5, 0.5, "full_attn/qkv"),
        op(4, 7.0, 1.0, "mlp/dense_mlp"),
    ]
    probe = Probe()
    probe.spans += [
        ("forward", 1.0, 1.1, {"valid_tokens": 100, "kv_read_tokens": 9000,
                               "qk_pairs": 500000}),
        ("forward", 6.0, 6.1, {"valid_tokens": 2, "kv_read_tokens": 9100,
                               "qk_pairs": 9100})]
    spans = [
        {"name": "forward", "t_start": 1.0, "t_end": 1.1, "attrs": _record()},
        {"name": "forward", "t_start": 6.0, "t_end": 6.1, "attrs": _record(
            valid_tokens=2, kv_bytes_resident=30, kv_g0_in_use=60,
            kv_g1_in_use=20, kv_g0_read_tokens=9100, kv_g0_qk_pairs=9100,
            kv_g1_read_tokens=6000, kv_g1_qk_pairs=6000,
            moe_rows_held=4 * 2 * 4 * 32 // 256)}]
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
    assert read("attn_window_share") == pytest.approx(100 * 2.5 / busy)
    assert read("attn_full_share") == pytest.approx(100 * 1.0 / busy)
    assert read("kv_resident_ratio") == pytest.approx((0.4 + 0.3) / 2)
    assert read("kv_window_blocks_peak_share") == pytest.approx(100 * 30 / 40)
    assert read("kv_full_blocks_peak_share") == pytest.approx(100 * 60 / 200)
    least = sum(
        layers * peaks.roofline_seconds(
            b.paged_attention_cost(arch, tokens, r, p), "TPU v5 lite")
        for tokens, groups in ((100, ((9000, 500000), (5000, 300000))),
                               (2, ((9100, 9100), (6000, 6000))))
        for (r, p), (_, layers) in zip(groups, b.attention_calls(arch)))
    assert read("paged_attn_window_roofline") == pytest.approx(
        100 * least / 2.5)
    # the readers the cell shares with the other sparse block read here too
    assert read("experts_share") == pytest.approx(100 * 1.5 / busy)
    # the block brings the grouped matmul's cost too; its reader is not
    # listed for the cell (above) and reads all the same
    assert hybrid_readers.gmm_roofline(ctx) == pytest.approx(
        100 * sum(peaks.roofline_seconds(b.gmm_cost(arch, t), "TPU v5 lite")
                  for t in (100, 2)) / 1.0)
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
    # a trace of a program without the names or the counters (the
    # parent's), and a block without the scopes or the cost functions:
    # nothing, and no error
    ctx, info = hand_made_context(monkeypatch)
    bare = [dict(e, op_name="") if "op_name" in e else e
            for e in scopes.load("")]
    monkeypatch.setattr(scopes, "load", lambda path: bare)
    ctx._scopes = scopes.summarize(bare, chips=1)
    ctx.result["program_spans"] = [
        {"name": "forward", "t_start": 1.0, "t_end": 1.1,
         "attrs": {"n_seqs": 2, "valid_tokens": 100}}]
    for name in NEW_READERS:
        assert mf.find_module(mf.HERE, "layer_metrics", name).reduce(ctx) \
            is None, name
    ctx.info = dict(info, block=mf.find_module(mf.HERE, "blocks", "dense"))
    for name in NEW_READERS:
        assert mf.find_module(mf.HERE, "layer_metrics", name).reduce(ctx) \
            is None, name
    # the program's groups are not the block's: no roofline is made up
    ctx, info = hand_made_context(monkeypatch)
    for s in ctx.result["program_spans"]:
        s["attrs"]["kv_g1_window"] = 1024
    assert kv_group_readers.paged_attention_roofline(ctx) is None


@pytest.mark.parametrize("traced", [0, 1])
def test_the_cell_rehearsed_on_the_cpu(checkout, capsys, traced):  # noqa: F811
    """The whole runner over the engine at the tiny twin's size, under
    the real names (the twin and its mix reach the checkout through
    ``tests/conftest.py``, found by name): requests in chunks across the
    window's edge, blocks handed back while they live, the logits check
    against this block's reference, every block of every group back."""
    path = os.path.join(checkout, "benchmark/workloads", CELL + ".json")
    _write(path, dict(_read(path), rate_rps=10.0, trace_s=1.0))
    manifest = mf.load(checkout)
    mf.validate(manifest, checkout)
    info = mf.resolve(manifest, CELL, checkout)
    assert info["config"] == twin()
    assert info["traffic"] == _read(os.path.join(TWINS, "traffic",
                                                 "mixedctx.json"))
    group = "per_layer" if traced else "end_to_end"
    line, extra = rehearse(checkout, capsys, CELL, traced)
    assert line["correct"], extra["why_not"]
    assert line["failed"] == 0 and line["attempted"] > 5
    check_line(line, manifest, CELL, group)
    check = extra["counters"]["logits_check"]
    assert 0 < check["max_rel_err"] < 1e-4 and check["rms_rel_err"] < 1e-4
    if traced:
        # off the chip the counters are read, the device is not
        assert {"kv_resident_ratio", "kv_window_blocks_peak_share",
                "kv_full_blocks_peak_share", "moe_rows_per_expert",
                "batch_seqs_mean"} <= set(line["metrics"])
        assert line["metrics"]["kv_resident_ratio"]["value"] < 1
        assert "attn_window_share" not in line["metrics"]
    else:
        assert set(line["metrics"]) == {"ttft_p90_ms", "tpot_p90_ms",
                                        "setup_s"}


# ------------------------------------------- ill-conditioned routing decisions

@pytest.mark.parametrize("held, want", [((0, 1), 0.15), ((1, 2), 0.05),
                                        ((3, 3), 0.2)],
                         ids=["one-in", "both-sides-of-the-edge", "all-out"])
def test_the_margin_of_a_routing_decision_against_hand_counts(held, want):
    """Scores of 0.5 everywhere (a router of zeros) and a bias that orders
    the six experts: score + bias 0.9, 0.8 | 0.75, 0.6, 0.55, 0.5 at top-2.
    A held expert that is in stays in while it beats the first one out
    (0.75); one that is out stays out while the last one in (0.8) beats it."""
    import jax.numpy as jnp

    b = block()
    lp = {"router_wg": jnp.zeros((8, 6)),
          "router_b": jnp.asarray([0.40, 0.30, 0.25, 0.10, 0.05, 0.0])}
    arch = {"moe_top_k": 2, "moe_norm_topk": True, "moe_route_scale": 1.0}
    weights, experts, margin = b._route(jnp.ones((3, 8)), lp, arch, held)
    assert experts.tolist() == [[0, 1]] * 3
    assert np.allclose(weights, 0.5) and np.allclose(margin, want)


def test_logits_answer_exactly_where_every_decision_is_well_conditioned(
        monkeypatch):
    import jax

    arch, model, params = tiny_model()
    b = block()
    tokens = jax.random.randint(jax.random.PRNGKey(5), (120,), 0,
                                arch["vocab_size"])
    whole, margin = map(np.asarray, b.tie_margins(params, tokens, arch,
                                                  q_block=16))
    assert np.isfinite(whole).all() and (margin > 0).all()
    # the middle margin as the limit: half the positions get no answer
    monkeypatch.setattr(b, "TIE_MARGIN", float(np.median(margin)))
    got = np.asarray(b.logits(params, tokens, arch, q_block=16))
    unanswered = np.isnan(got).all(axis=-1)
    assert (unanswered == (margin < b.TIE_MARGIN)).all()
    assert 40 < unanswered.sum() < 80
    assert (got[~unanswered] == whole[~unanswered]).all()


def test_an_unanswered_position_is_not_compared_and_an_answered_one_is(
        monkeypatch):
    """Through the harness's own ``check_logits``: a reference row that is
    NaN throughout is masked out and counted. An engine that is wrong at
    every position (its selection bias dropped) is caught as long as one
    compared position is answered — and a limit so wide that none is
    would pass anything (``compared: 0``), which is why ``TIE_MARGIN`` is
    a few rounding errors and not more."""
    import jax

    from benchmark import serve_runner as sr
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2

    config = twin()
    config["engine"] = dict(config["engine"], compile_ahead=0)
    info = {"config": config, "block": block()}
    _, params, engine = sr.build(info, 5)
    b, arch = info["block"], config["transformer_config"]
    prompt = np.random.default_rng(8).integers(
        0, arch["vocab_size"], size=70).tolist()
    wrong = jax.tree_util.tree_map_with_path(
        lambda path, a: a * 0 if "router_b" in str(path[-1]) else a, params)
    spoiled = InferenceEngineV2(engine.model, params=wrong,
                                config=engine.config)

    def check(limit):
        monkeypatch.setattr(b, "TIE_MARGIN", limit)
        return sr.check_logits(spoiled, params, info, [prompt], 2, 1e-4, 1e-4)

    # every position answered: caught
    record = check(0.0)
    assert not record["ok"] and record["max_rel_err"] > 1e-3
    # the margins of the three compared positions (the prompt's last and
    # two decode steps; the greedy tokens are the spoiled engine's)
    tokens = list(prompt)
    for at in range(0, 70, 32):
        out = spoiled.put([77], [prompt[at:at + 32]])
    for _ in range(2):
        tokens.append(int(np.argmax(np.asarray(out[0]))))
        out = spoiled.put([77], [[tokens[-1]]])
    spoiled.flush(77)
    seen = sorted(np.asarray(b.tie_margins(
        params, np.asarray(tokens + [0] * 10, np.int32), arch)[1])[69:72]
        .tolist())
    # one position unanswered, two compared: still caught
    record = check((seen[0] + seen[1]) / 2)
    assert not record["ok"]
    assert (record["compared"], record["unanswered"]) == (2, 1)
    # none answered: nothing is compared, and that passes
    record = check(seen[-1] * 2)
    assert record["ok"]
    assert (record["compared"], record["unanswered"]) == (0, 3)
