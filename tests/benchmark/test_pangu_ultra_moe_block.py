"""The ``pangu_ultra_moe`` block as the benchmark finds it: the manifest
with its entries, the configuration against the catalog row it was drawn
from, the reference against the program's model at the tiny twin's size —
``CausalLM.apply``, and prefill in chunks then decode through the latent
cache on both attention paths, with the faults that must show — the
sixteen shares against the uncut layer, the arithmetic against hand
counts, the scope names, the new readers on hand-made contexts, and the
cell rehearsed end to end on the CPU under the real names."""

import json
import os

import numpy as np
import pytest
from test_benchmark_runners import (_read, _write, check_line,  # noqa: F401
                                    checkout, rehearse)

from benchmark import latent_readers, peaks, scopes, trace
from benchmark import manifest as mf
from benchmark.model import check_consistent
from benchmark.probe import Probe
from benchmark.run import Context

CELL, CONFIG = "openpangu-ultra-moe-718b.longprompt", "openpangu-ultra-moe-718b"
NEW_READERS = ("latent_attn_share", "kv_expand_share", "kv_expand_ratio",
               "mla_decode_roofline", "mla_prefill_roofline")
#: the accepted readers the cell lists: those that move the one
#: end-to-end metric it is judged on
SHARED_READERS = ("gen_late_p99_ms", "queue_wait_p50_ms",
                  "kv_blocks_peak_share", "fwd_mixed_dev_ms")
CATALOG_FILE = "/opt/skills/guides/model-configs/architectures.jsonl"
TWINS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "twins")
#: the catalog row's ``config`` (model-configs guide, architectures.jsonl,
#: ``openPangu-Ultra-MoE-718B``), as the issue drew it
CATALOG = {
    "attention_bias": False, "first_k_dense_replace": 3, "hidden_act": "silu",
    "hidden_size": 7680, "intermediate_size": 18432, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "pangu_ultra_moe",
    "moe_intermediate_size": 2048, "n_routed_experts": 256,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 128,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_theta": 25600000, "routed_scaling_factor": 2.5,
    "sandwich_norm": True, "tie_word_embeddings": False, "v_head_dim": 128,
    "vocab_size": 153600}


def block():
    return mf.find_module(mf.HERE, "blocks", "pangu_ultra_moe")


def real():
    manifest = mf.load()
    return manifest, mf.resolve(manifest, CELL)


def twin():
    return _read(os.path.join(TWINS, "configs", CONFIG + ".json"))


def test_the_manifest_validates_with_the_new_entries():
    manifest, info = real()
    mf.validate(manifest)
    # (no pin on the totals: a later PR appends, and may not edit this file)
    assert len(manifest["workloads"]) >= 7
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) \
        <= max(1, len(manifest["workloads"]) // 4)
    assert info["block"].__name__.endswith("pangu_ultra_moe")
    assert info["traffic"]["loop"] == "open"
    assert info["traffic"]["generator"] == "stratified"
    assert info["cell"]["chips"] == 1
    mine = {m["name"] for m in mf.metrics_for(manifest, "per_layer", CELL)}
    assert set(NEW_READERS) | set(SHARED_READERS) <= mine
    # the rooflines whose cost is [KH, bs, D]-shaped or an expectation
    # that read over 100% (PERF.md section 7), and what only a recurrent
    # or a windowed block has, are not this cell's
    assert not mine & {"paged_attn_roofline", "paged_attn_hybrid_roofline",
                       "paged_attn_window_roofline", "gmm_roofline",
                       "gdn_share", "state_slots_peak_share",
                       "attn_window_share", "kv_resident_ratio"}
    # judged on TTFT alone: with 31 requests a window TPOT's p90 is one
    # request's gap, which reads 24.6-26.4 ms as it sits behind one chunk
    # forward more or fewer (PERF.md section 6) - so the cell lists what
    # moves ``ttft_p90_ms`` and nothing that moves ``tpot_p90_ms``
    assert {m["name"] for m in mf.metrics_for(manifest, "end_to_end", CELL)} \
        == {"ttft_p90_ms", "setup_s"}
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert all(by_name[name]["moves"] == "ttft_p90_ms" for name in mine)
    doc = {m["name"] for m in mf.metrics_for(manifest, "per_layer",
                                             "pythia-1.4b.doc")
           if m["moves"] == "ttft_p90_ms"}
    assert doc <= mine
    # listed for this cell first; a later cell that reads the same names
    # is appended behind it
    for m in manifest["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"][0] == CELL
    # the new entries were appended: behind what PR 34 left last
    at = lambda group, name: [e["name"] for e in manifest[group]  # noqa: E731
                              ].index(name)
    assert at("configs", CONFIG) > at("configs", "trinity-large-preview")
    assert at("workloads", CELL) > at("workloads",
                                      "trinity-large-preview.mixedctx")
    first = at("per_layer", NEW_READERS[0])
    assert first > at("per_layer", "paged_attn_window_roofline")
    assert [m["name"] for m in manifest["per_layer"][first:first + 5]] \
        == list(NEW_READERS)


def test_the_configuration_is_the_catalog_rows_but_for_what_it_reduces():
    manifest, info = real()
    config, entry = info["config"], info["config_entry"]
    assert entry["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                                "n_routed_experts", "vocab_size"]
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
    assert arch["moe_num_experts"] == CATALOG["n_routed_experts"]
    assert arch["moe_held_experts"] == [0, config["n_routed_experts"]]
    assert arch["moe_top_k"] == 8 and arch["moe_route_scale"] == 2.5
    assert arch["moe_select_bias"] is False
    assert arch["moe_shared_gate"] is False
    assert arch["lead_layers"] == ["latent"] * config["first_k_dense_replace"]
    # one period holds the four sparse layers: a scan of one iteration,
    # whose stacked weights are not sliced (the file's engine._steps)
    assert arch["layer_pattern"] == ["latent"] * 4
    assert arch["num_layers"] == config["num_hidden_layers"] == 5
    assert arch["max_seq_len"] == 32768 + 1024
    for key in ("assumed", "deployment", "published", "reduced", "engine",
                "check"):
        assert config[key], key
    assert "multi-token-prediction" in config["assumed"]["left_out"]
    assert "16 v5e chips share each layer" in config["deployment"]
    assert "1,280" in config["engine"]["_arithmetic"]
    # the twin has the file's shape: the same block, the same switches
    small = twin()
    assert small["block"] == config["block"]
    assert set(small["transformer_config"]) == set(arch)
    if os.path.isfile(CATALOG_FILE):        # the row itself, where it is
        with open(CATALOG_FILE) as f:
            rows = [json.loads(line) for line in f if line.strip()]
        row = next(r for r in rows if r["source_url"] == entry["source"])
        assert row["config"] == CATALOG


@pytest.mark.parametrize("wrong", [{"kv_lora_rank": 256},
                                   {"qk_rope_head_dim": 32},
                                   {"num_experts_per_tok": 4},
                                   {"routed_scaling_factor": 1.0},
                                   {"v_head_dim": 192},
                                   {"moe_intermediate_size": 1024}])
def test_a_published_key_that_disagrees_with_the_program_is_refused(wrong):
    _, info = real()
    with pytest.raises(ValueError, match=next(iter(wrong))):
        check_consistent(dict(info["config"], **wrong), info["block"])


def test_matmul_params_and_kernel_costs_against_hand_counts():
    _, info = real()
    arch, b = info["config"]["transformer_config"], info["block"]
    attention = (7680 * 1536 + 1536 * 128 * 192 + 7680 * 576
                 + 512 * 128 * 256 + 128 * 128 * 7680)
    assert b.attention_matmul_params(arch) == attention == 196_575_232
    assert b.expert_matmul_params(arch) == 3 * 7680 * 2048 == 47_185_920
    assert b.layer_kinds(arch) == {"latent": 5, "lead": 1, "sparse": 4}
    sparse = 7680 * 256 + 47_185_920 + 8 * (16 / 256) * 47_185_920
    assert b.matmul_params(arch) == pytest.approx(
        5 * attention + 3 * 7680 * 18432 + 4 * sparse + 7680 * 19200)
    assert peaks.forward_flops(b, arch, 1, 0) == 2.0 * b.matmul_params(arch)
    # the issue's arithmetic: 278.5 k a pair absorbed, 81.9 k expanded,
    # 33.6 M a key rebuilt, and where the two cross
    dec = b.mla_decode_cost(arch, 4, 9000, 9000)
    assert dec["flops"] == 2.0 * 128 * (576 + 512) * 9000 == 278_528 * 9000
    assert dec["bytes"] == 1152 * 9000 + 128 * 1088 * 2 * 4
    pre = b.mla_prefill_cost(arch, 2048, 6144, 2048 * 4096 + 2048 * 2049 // 2)
    assert pre["flops"] == 81_920 * (2048 * 4096 + 2048 * 2049 // 2)
    assert pre["bytes"] == 2 * ((128 * 256 + 64) * 6144 + 128 * 320 * 2048)
    assert b.kv_expand_flops(arch, 1) == 33_554_432
    from deepspeed_tpu.ops import latent_attention as la

    cross = b.kv_expand_flops(arch, 1) / (278_528 - 81_920)
    assert 170 < cross < 171
    assert la.ABSORB_MAX_QUERIES < cross < 2 * la.ABSORB_MAX_QUERIES
    # the decode kernel sits at the chip's ridge: FLOPs a byte of latent
    assert 241 < 278_528 / 1152 < 242


def tiny_model(**overrides):
    import jax.numpy as jnp

    from benchmark.model import seeded_params
    from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig

    arch = dict(twin()["transformer_config"], **overrides)
    model = CausalLM(TransformerConfig(**dict(arch, dtype=jnp.float32)))
    return arch, model, seeded_params(model, 3, jnp.float32)


@pytest.mark.parametrize("overrides", [
    {}, {"moe_held_experts": None},
    {"lead_layers": [], "num_layers": 4, "layer_pattern": ["latent"]},
    {"lead_layers": ["latent", "latent"], "num_layers": 4,
     "qk_rope_head_dim": 16, "v_head_dim": 8},
], ids=["published-shape", "all-held", "no-lead-period-of-one",
        "two-lead-other-widths"])
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


# ------------------------------------------ through the latent cache

PROMPT, STEPS = 90, 6


def _engine(model, params, **sizing):
    from deepspeed_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2, RaggedInferenceEngineConfig)

    return InferenceEngineV2(model, params=params,
                             config=RaggedInferenceEngineConfig(**dict(
                                 twin()["engine"], compile_ahead=0, **sizing)))


def _served(engine, tokens, uid=7):
    """Prefill in 32-token chunks, then decode the given tokens: the
    logits at the prompt's last position and at every later one."""
    got = []
    for at in range(0, PROMPT, 32):
        out = engine.put([uid], [tokens[at:min(at + 32, PROMPT)]])
    got.append(np.asarray(out[0]))
    for i in range(PROMPT, PROMPT + STEPS):
        got.append(np.asarray(engine.put([uid], [[tokens[i]]])[0]))
    return np.stack(got)


def _worst(got, want):
    return np.abs(got - want[PROMPT - 1:PROMPT + STEPS]).max() \
        / np.abs(want).max()


@pytest.fixture()
def served(monkeypatch):
    """The twin's model, a prompt, the reference's logits, and the two
    paths' settings: ``absorbed`` keeps every forward on the absorbed
    path (chunks of 32 under the switch at 128), ``expanded`` moves the
    switch under the chunk and shortens the tile, so that chunks rebuild
    K/V — the later ones from earlier blocks, two tiles of 16 keys a
    32-token chunk and more — and one-token rows stay absorbed."""
    from deepspeed_tpu.ops import latent_attention as la

    arch, model, params = tiny_model()
    tokens = np.random.default_rng(4).integers(
        0, arch["vocab_size"], size=PROMPT + STEPS).tolist()
    want = np.asarray(block().tie_margins(
        params, np.asarray(tokens, np.int32), arch, q_block=16)[0])

    def path(name):
        if name == "expanded":
            monkeypatch.setattr(la, "ABSORB_MAX_QUERIES", 8)
            monkeypatch.setattr(la, "EXPAND_TILE", 16)
        return la

    return arch, model, params, tokens, want, path


@pytest.mark.parametrize("name", ["absorbed", "expanded"])
def test_chunks_then_decode_through_the_latent_cache(served, name):
    arch, model, params, tokens, want, path = served
    path(name)
    engine = _engine(model, params)
    sm = engine.state_manager
    assert set(sm.kv_cache) == {"kv"}
    assert sm.kv_cache["kv"].shape == (3, 128, 8, 128)      # no head axis
    assert _worst(_served(engine, tokens), want) < 1e-5
    totals = engine.put_totals
    assert totals["prefill_tokens"] == PROMPT
    if name == "absorbed":
        assert totals["latent_q_expanded"] == 0
        assert totals["latent_q_absorbed"] == PROMPT + STEPS
    else:
        assert totals["latent_q_expanded"] == PROMPT
        assert totals["latent_q_absorbed"] == STEPS
        # chunks of 32, 32 and 26 rebuild their contexts: 32 + 64 + 96
        # (90 in whole tiles of 16)
        assert totals["latent_rows_expanded"] == 32 + 64 + 96
    engine.flush(7)
    assert sm.available_blocks == 128
    assert len(sm._free_id_slots) == sm.id_slots


def _spoiled(params, leaf, change):
    import jax

    return jax.tree_util.tree_map_with_path(
        lambda path, a: change(a) if leaf in str(path[-1]) else a, params)


@pytest.mark.parametrize("name", ["absorbed", "expanded"])
@pytest.mark.parametrize("fault", ["norm-gain", "k_r-dropped",
                                   "softmax-scale", "block-lost"])
def test_each_fault_fails_the_comparison(served, name, fault, monkeypatch):
    """A perturbed inner norm gain, a ``k_r`` that never reaches the
    cache's rows, the softmax scale of the nope width alone (1/sqrt(16)
    for 1/sqrt(24)) and a live block of the table pointing at a
    neighbour's each disagree with the reference, on either path."""
    from deepspeed_tpu.models import hybrid

    arch, model, params, tokens, want, path = served
    path(name)
    if fault == "norm-gain":
        params = _spoiled(params, "kv_a_norm_w", lambda a: a * 1.1)
    elif fault == "k_r-dropped":
        real_qkv = hybrid.latent_qkv

        def no_k_r(cfg, h1, lp, rope):
            q_nope, q_rope, c, k_r = real_qkv(cfg, h1, lp, rope)
            return q_nope, q_rope, c, k_r * 0

        monkeypatch.setattr(hybrid, "latent_qkv", no_k_r)
    elif fault == "softmax-scale":
        monkeypatch.setattr(hybrid, "latent_scale",
                            lambda cfg: cfg.qk_nope_head_dim ** -0.5)
    engine = _engine(model, params)
    if fault == "block-lost":
        # from its third chunk on, sequence 7's third block (written by
        # its first) reads another sequence's rows
        engine.put([8], [tokens[:24]])
        real_rows = engine.state_manager.table_rows

        def lost(seq):
            rows = real_rows(seq).copy()
            if seq.uid == 7 and seq.seen_tokens >= 64:
                rows[..., 2] = engine.state_manager.get_sequence(
                    8).kv_blocks[0]
            return rows

        monkeypatch.setattr(engine.state_manager, "table_rows", lost)
    assert _worst(_served(engine, tokens), want) > 1e-3


def test_the_sixteen_shares_and_the_shared_expert_once_add_up_to_the_layer():
    """The share is the model: routed over all 16 experts, each share's
    routed part over its own expert, summed over the sixteen shares, plus
    the shared expert once, is the uncut layer's FFN — in the reference,
    and in the program's ``moe_ffn`` given each share's weights."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models import hybrid
    from deepspeed_tpu.models.transformer import TransformerConfig

    arch, model, params = tiny_model(moe_held_experts=None)
    b = block()
    lp = jax.tree.map(lambda a: a[0], params["layers"]["slot0"])
    h = jax.random.normal(jax.random.PRNGKey(4), (50, arch["hidden_size"]))
    E = arch["moe_num_experts"]
    assert E == 16
    with jax.default_matmul_precision("highest"):
        whole = b.routed_part(h, lp, arch, held=(0, E)) + b.shared_part(h, lp)
        uncut = hybrid.moe_ffn(TransformerConfig(**dict(
            arch, dtype=jnp.float32)), h[None], lp)[0][0]
        parts, program = [], []
        for lo in range(E):
            share = dict(lp, **{k: lp[k][lo:lo + 1]
                                for k in ("w_in", "w_gate", "w_out")})
            parts.append(b.routed_part(h, share, arch, held=(lo, 1)))
            cfg = TransformerConfig(**dict(arch, dtype=jnp.float32,
                                           moe_held_experts=(lo, 1)))
            program.append(hybrid.moe_ffn(cfg, h[None], share)[0][0])
        shared = b.shared_part(h, lp)
    assert np.allclose(uncut, whole, atol=1e-5)
    assert np.allclose(sum(parts) + shared, whole, atol=1e-5)
    # each share of the program carries the shared expert: once is kept
    assert np.allclose(sum(program) - 15 * shared, whole, atol=1e-4)
    # top-4 of 16: most shares see routed work, none sees all of it
    assert sum(float(jnp.abs(p).max()) > 0 for p in parts) > 8


def test_the_new_scope_names_resolve_through_the_blocks_scopes():
    b = block()
    body = "jit(_forward)/layers/while/body/closed_call/"
    cases = {
        body + "latent_attn/attend/mla_decode/pallas_call:": "attend",
        body + "latent_attn/while/body/attend/mla_prefill/pallas_call:":
            "attend",
        body + "latent_attn/while/body/kv_expand/dot_general:": "kv_expand",
        body + "latent_attn/qkv/dot_general:": "qkv",
        body + "latent_attn/kv_write/scatter:": "kv_write",
        body + "latent_attn/attn_out/dot_general:": "attn_out",
        body + "latent_attn/mul:": "latent_attn",
        "jit(_forward)/layers/latent_attn/qkv/dot_general:": "qkv",
        "jit(_forward)/layers/mlp/dense_mlp/dot_general:": "dense_mlp",
        body + "mlp/router/dot_general:": "router",
        body + "mlp/experts/jit(gmm)/pallas_call:": "experts",
        body + "mlp/shared_expert/dot_general:": "shared_expert",
        body + "dynamic_slice:": scopes.SCAN_OVERHEAD,
    }
    for op_name, want in cases.items():
        assert scopes.scope_of(op_name, b.SCOPES) == want, op_name
    assert "latent_attn" in scopes.scope_path(
        body + "latent_attn/while/body/kv_expand/dot_general:", b.SCOPES)
    assert set(b.ATTN_SCOPES.values()) < set(b.SCOPES)


def _ev(line, name, start, dur, plane="/device:TPU:0", **extra):
    return dict(plane=plane, line=line, name=name, start=start, dur=dur,
                **extra)


def _record(**over):
    """A put's record as the program's ``forward`` span carries it."""
    base = {"n_seqs": 1, "valid_tokens": 2048, "kv_read_tokens": 6144,
            "qk_pairs": 2048 * 4096 + 2048 * 2049 // 2,
            "prefill_tokens": 2048, "latent_q_absorbed": 0,
            "latent_keys_absorbed": 0, "latent_pairs_absorbed": 0,
            "latent_q_expanded": 2048, "latent_rows_expanded": 8192,
            "moe_rows_held": 4 * 2048 * 8 * 16 // 256}
    return dict(base, **over)


DECODE = dict(valid_tokens=4, kv_read_tokens=36000, qk_pairs=36000,
              prefill_tokens=0, latent_q_absorbed=4,
              latent_keys_absorbed=36000, latent_pairs_absorbed=36000,
              latent_q_expanded=0, latent_rows_expanded=0,
              moe_rows_held=4 * 4 * 8 * 16 // 256)


def hand_made_context(monkeypatch):
    """10 s window, three executions of the forward. A chunk 1..5
    ([1x2048]): the latent layer's qkv 1..1.5, kv_expand 1.5..2.5, its
    expanded kernel 2.5..4, experts 4..5. A decode step 6..8 ([4x1]): the
    absorbed kernel 6..6.5, attn_out 6.5..7, the dense MLP 7..8. A second
    chunk began at 8.5 and is still running when the profiler stops."""
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
        _ev("python3", "bench:forward[1x2048]", 0.9, 0.2, plane="/host:CPU"),
        _ev("python3", "bench:forward[4x1]", 5.9, 0.2, plane="/host:CPU"),
        _ev(trace.MODULES_LINE, "jit__forward(1)", 1.0, 4.0),
        _ev(trace.MODULES_LINE, "jit__forward(2)", 6.0, 2.0),
        op(1, 1.0, 0.5, "latent_attn/qkv"),
        op(2, 1.5, 1.0, "latent_attn/while/body/kv_expand"),
        kernel("mla_prefill", 2.5, 1.5,
               "latent_attn/while/body/attend/mla_prefill"),
        op(3, 4.0, 1.0, "mlp/experts"),
        kernel("mla_decode", 6.0, 0.5, "latent_attn/attend/mla_decode"),
        op(4, 6.5, 0.5, "latent_attn/attn_out"),
        op(5, 7.0, 1.0, "mlp/dense_mlp"),
    ]
    probe = Probe()
    spans = [
        {"name": "forward", "t_start": 1.0, "t_end": 1.1, "attrs": _record()},
        {"name": "forward", "t_start": 6.0, "t_end": 6.1,
         "attrs": _record(**DECODE)},
        {"name": "forward", "t_start": 8.5, "t_end": 8.6, "attrs": _record()}]
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
    assert read("latent_attn_share") == pytest.approx(100 * 4.0 / busy)
    assert read("kv_expand_share") == pytest.approx(100 * 1.0 / busy)
    # two chunks of 2,048 at a context of 6,144, in tiles of 4,096
    assert read("kv_expand_ratio") == pytest.approx(2 * 8192 / (2 * 2048))
    # the forward still running at the end is left out of the least work
    seconds = lambda cost: peaks.roofline_seconds(cost, "TPU v5 lite")  # noqa: E731
    assert read("mla_prefill_roofline") == pytest.approx(
        100 * 5 * seconds(b.mla_prefill_cost(
            arch, 2048, 6144, 2048 * 4096 + 2048 * 2049 // 2)) / 1.5)
    assert read("mla_decode_roofline") == pytest.approx(
        100 * 5 * seconds(b.mla_decode_cost(arch, 4, 36000, 36000)) / 0.5)
    assert read("experts_share") == pytest.approx(100 * 1.0 / busy)
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
    ctx, info = hand_made_context(monkeypatch)
    ctx.info = dict(info, block=mf.find_module(mf.HERE, "blocks", "dense"))
    for name in ("latent_attn_share", "mla_decode_roofline",
                 "mla_prefill_roofline"):
        assert mf.find_module(mf.HERE, "layer_metrics", name).reduce(ctx) \
            is None, name
    assert latent_readers.forward_records(ctx, 20.0, 30.0) == []


@pytest.mark.parametrize("traced", [0, 1])
def test_the_cell_rehearsed_on_the_cpu(checkout, capsys, traced,  # noqa: F811
                                       monkeypatch):
    """The whole runner over the engine at the tiny twin's size, under
    the real names (the twin and its mix reach the checkout through
    ``tests/conftest.py``, found by name): prompts in several chunks
    beside decoding rows, the logits check against this block's
    reference, every block back."""
    path = os.path.join(checkout, "benchmark/workloads", CELL + ".json")
    _write(path, dict(_read(path), rate_rps=10.0, trace_s=1.0))
    manifest = mf.load(checkout)
    mf.validate(manifest, checkout)
    info = mf.resolve(manifest, CELL, checkout)
    assert info["config"] == twin()
    assert info["traffic"] == _read(os.path.join(TWINS, "traffic",
                                                 "longprompt.json"))
    group = "per_layer" if traced else "end_to_end"
    if traced:
        # the twin's 32-token chunks are under the switch at 128: moved
        # under them, the traced rehearsal rebuilds K/V in tiles of 16
        # (the untraced one stays absorbed throughout)
        from deepspeed_tpu.ops import latent_attention as la

        monkeypatch.setattr(la, "ABSORB_MAX_QUERIES", 8)
        monkeypatch.setattr(la, "EXPAND_TILE", 16)
    line, extra = rehearse(checkout, capsys, CELL, traced)
    assert line["correct"], extra["why_not"]
    assert line["failed"] == 0 and line["attempted"] > 5
    check_line(line, manifest, CELL, group)
    check = extra["counters"]["logits_check"]
    assert 0 < check["max_rel_err"] < 1e-4 and check["rms_rel_err"] < 1e-4
    if traced:
        # off the chip the counters are read, the device is not
        assert {"kv_expand_ratio", "kv_blocks_peak_share",
                "queue_wait_p50_ms"} <= set(line["metrics"])
        # prompts of 16-120 in chunks of 32: L / 2C + 1/2 is 0.75-2.4
        assert 1 <= line["metrics"]["kv_expand_ratio"]["value"] < 3
        assert "latent_attn_share" not in line["metrics"]
        assert "mla_decode_roofline" not in line["metrics"]
    else:
        assert set(line["metrics"]) == {"ttft_p90_ms", "setup_s"}
