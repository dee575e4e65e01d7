"""The ``dots3_note`` block as the benchmark finds it: the manifest with
its entries, the configuration against the catalog row it was drawn from,
the reference against the program's model at the tiny twin's size —
``CausalLM.apply``, and prefill in chunks then decode through both pools
on both attention paths — the selected sets against the reference's, a
sparse layer that attended everything, the window group's blocks coming
back, the typed refusals, the shares against the uncut layer, the
arithmetic against the issue's counts, the scope names, the new readers
on hand-made contexts, and the cell rehearsed end to end on the CPU under
the real names. The twin's selection keeps 8 keys and its window 5, so at
contexts of 40-100 both cut. The kernels' Pallas bodies against their XLA
twins are tests/test_latent_attention.py's."""

import json
import os

import numpy as np
import pytest
from test_benchmark_runners import (_read, _write, check_line,  # noqa: F401
                                    checkout, rehearse)

from benchmark import manifest as mf
from benchmark import scopes, sparse_readers
from benchmark.model import check_consistent

CELL, CONFIG = "dots3-note-prev.longctx", "dots3-note-prev"
NEW_READERS = ("sparse_index_share", "index_select_share",
               "sparse_select_ratio", "window_latent_attn_share",
               "mla_sparse_roofline", "index_score_roofline",
               "mla_window_roofline")
SHARED_READERS = ("gen_late_p99_ms", "queue_wait_p50_ms",
                  "kv_blocks_peak_share", "fwd_mixed_dev_ms",
                  "dev_prefill_us_per_token", "prefill_own_share",
                  "kv_resident_ratio", "kv_window_blocks_peak_share",
                  "kv_full_blocks_peak_share", "latent_attn_share",
                  "kv_expand_share", "kv_expand_ratio")
CATALOG_FILE = "/opt/skills/guides/model-configs/architectures.jsonl"
TWINS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "twins")
PROMPT, STEPS = 90, 6


def block():
    return mf.find_module(mf.HERE, "blocks", "dots3_note")


def real():
    manifest = mf.load()
    return manifest, mf.resolve(manifest, CELL)


def twin():
    return _read(os.path.join(TWINS, "configs", CONFIG + ".json"))


# ------------------------------------------------------------ the manifest

def test_the_manifest_validates_with_the_new_entries():
    manifest, info = real()
    mf.validate(manifest)
    # (no pin on the totals: a later PR appends, and may not edit this file)
    assert len(manifest["workloads"]) >= 8
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) \
        <= max(1, len(manifest["workloads"]) // 4)
    assert info["block"].__name__.endswith("dots3_note")
    assert info["traffic"]["loop"] == "open"
    assert info["traffic"]["generator"] == "stratified"
    assert info["traffic"]["prompt_tokens"] == {
        "median": 16384, "sigma": 0.8, "min": 4096, "max": 65536}
    assert info["cell"]["chips"] == 1
    mine = {m["name"] for m in mf.metrics_for(manifest, "per_layer", CELL)}
    assert set(NEW_READERS) | set(SHARED_READERS) <= mine
    # kernels this model's layers do not run, a recurrent state it has
    # not, and the names that would read a second time what a new metric
    # reads (PERF.md section 4)
    assert not mine & {"mla_decode_roofline", "mla_prefill_roofline",
                       "paged_attn_roofline", "paged_attn_window_roofline",
                       "gmm_roofline", "gdn_share", "state_slots_peak_share",
                       "attn_window_share", "attn_full_share"}
    ends = {m["name"] for m in mf.metrics_for(manifest, "end_to_end", CELL)}
    assert {"ttft_p90_ms", "setup_s"} <= ends <= {"ttft_p90_ms", "setup_s",
                                                  "tpot_p90_ms"}
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert all(by_name[name]["moves"] in ends for name in mine)
    for name in NEW_READERS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "ttft_p90_ms"
    at = lambda group, name: [e["name"] for e in manifest[group]  # noqa: E731
                              ].index(name)
    assert at("configs", CONFIG) > at("configs", "openpangu-ultra-moe-718b")
    assert at("workloads", CELL) > at("workloads",
                                      "openpangu-ultra-moe-718b.longprompt")
    assert at("per_layer", NEW_READERS[0]) > at("per_layer",
                                                "sat_idle_launch_share")


def test_the_configuration_is_the_catalog_rows_but_for_what_it_reduces():
    manifest, info = real()
    config, entry = info["config"], info["config_entry"]
    with open(CATALOG_FILE) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == CONFIG)
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == ["num_hidden_layers", "layer_types",
                                "n_routed_experts", "vocab_size"]
    assert sorted(config["reduced"]) == sorted(entry["reduced"])
    for key, value in row["config"].items():
        if key not in entry["reduced"]:
            assert config[key] == value, key
    assert config["published"]["n_routed_experts"] == 256
    assert config["layer_types"] == [row["config"]["layer_types"][i]
                                     for i in (0, 2, 3, 4, 5)]
    check_consistent(config, info["block"])
    check_consistent(twin(), info["block"])
    arch = config["transformer_config"]
    assert arch["lead_layers"] == ["latent_sparse"]
    assert arch["layer_pattern"] == ["latent_window"] * 3 + ["latent_sparse"]
    assert arch["moe_held_experts"] == [0, config["n_routed_experts"]]
    assert arch["max_seq_len"] == 66560
    for key in ("two_norms", "rescale", "gate", "indexer", "window",
                "left_out", "latent_mixer"):
        assert config["assumed"][key]
    # the twin keeps every switch of the published file's architecture
    tw = twin()["transformer_config"]
    assert set(tw) == set(arch)
    assert all(tw[k] == arch[k] for k in arch
               if isinstance(arch[k], (bool, str, list)) and k != "dtype"
               and k != "moe_held_experts")


def test_the_arithmetic_is_the_issues():
    _, info = real()
    b, arch = info["block"], info["config"]["transformer_config"]
    M = 1e6
    assert b.attention_matmul_params(arch, "latent_sparse") / M \
        == pytest.approx(134.68 + 9.37, abs=0.02)
    assert b.attention_matmul_params(arch, "latent_window") / M \
        == pytest.approx(90.83, abs=0.01)
    assert b.expert_matmul_params(arch) / M == pytest.approx(23.59, abs=0.01)
    assert b.layer_kinds(arch) == {"latent_sparse": 2, "latent_window": 3,
                                   "lead": 1, "sparse": 4}
    # what the program's model holds: 4,087 M parameters less the norms
    from deepspeed_tpu.models.transformer import TransformerConfig
    import jax

    from deepspeed_tpu.models.transformer import CausalLM
    import jax.numpy as jnp
    shapes = jax.eval_shape(CausalLM(TransformerConfig(**dict(
        arch, dtype=jnp.bfloat16))).init, jax.random.PRNGKey(0))
    total = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert total / M == pytest.approx(4087, abs=2)
    # per pair: a window layer absorbed 2 x (1088 + 1024), expanded
    # 2 x (256 + 128) a head; the rebuild 41.9 MFLOP a key
    assert b.kv_expand_flops(arch, "latent_window", 1) / M \
        == pytest.approx(41.9, abs=0.1)
    w = b.mla_window_cost(arch, 1, 1, 1, 0, 0, 0)
    assert w["flops"] == 2.0 * 64 * (1088 + 1024)
    w = b.mla_window_cost(arch, 0, 0, 0, 1, 1, 1)
    assert w["flops"] == 2.0 * 64 * (256 + 128)
    # the indexer: 16 kFLOP a key a query; sparse attention 570 MFLOP a
    # token a layer over 2,048 keys
    assert b.index_score_cost(arch, 1, 1, 1)["flops"] == 2.0 * 64 * 128
    assert b.mla_sparse_decode_cost(arch, 1, 2048)["flops"] / M \
        == pytest.approx(570, abs=1)
    assert b.latent_bytes(arch, "latent_sparse") == 1152
    assert b.latent_bytes(arch, "latent_window") == 2176


# ----------------------------------------- the reference and the program

@pytest.fixture(scope="module")
def tiny():
    """The twin's model and weights, a prompt and the reference's answer
    to it, built once."""
    import jax.numpy as jnp

    from benchmark.model import seeded_params
    from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig

    arch = twin()["transformer_config"]
    model = CausalLM(TransformerConfig(**dict(arch, dtype=jnp.float32)))
    params = seeded_params(model, 3, jnp.float32)
    tokens = np.random.default_rng(4).integers(
        0, arch["vocab_size"], size=PROMPT + STEPS).tolist()
    want, route, select = block()._logits_one(
        params, np.asarray(tokens, np.int32), arch, 16)
    return arch, model, params, tokens, np.asarray(want), \
        np.asarray(route), np.asarray(select)


def test_reference_agrees_with_the_programs_model(tiny):
    import jax
    import jax.numpy as jnp

    arch, model, params, tokens, want, _, select = tiny
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(model.apply)(
            params, jnp.asarray(tokens)[None]))[0]
    assert np.abs(got - want).max() < 2e-6 * (want.max() - want.min())
    # the selection cuts from the ninth position on, the window from the
    # sixth: every later position's margin is finite
    assert np.isinf(select[:8]).all() and np.isfinite(select[8:]).all()
    ids = np.asarray([tokens + tokens[:1]], np.int32)
    program, aux = jax.jit(lambda p: model.apply(
        p, ids[:, :-1], return_aux=True))(params)
    logp = jax.nn.log_softmax(program[0], -1)
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
        / (want.max() - want.min())


@pytest.fixture(scope="module")
def paths(tiny):
    """The prompt served once on either path, with what each engine
    counted: ``absorbed`` keeps every forward absorbed (chunks of 32
    under the whole-context switch at 128; the twin's window never
    crosses), ``expanded`` moves both kinds' switches under the chunk and
    shortens the tile, so that chunks rebuild K/V — the sparse layers'
    under the selection's mask, the window layers' from the window's
    tile on — and one-token rows stay absorbed. On both the scores are
    taken at the narrowest of three widths (16, 64, the table's 256 keys)
    that holds the context: the first two chunks at 64, the rest at 256."""
    from deepspeed_tpu.inference.v2 import paged_model
    from deepspeed_tpu.ops import latent_attention as la

    arch, model, params, tokens, *_ = tiny
    out = {}
    for name in ("absorbed", "expanded"):
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(paged_model, "SELECT_WIDTHS", (16, 64))
            if name == "expanded":
                patch.setattr(la, "ABSORB_MAX_QUERIES", 8)
                patch.setattr(la, "absorb_max_queries", lambda *a, **k: 8)
                patch.setattr(la, "EXPAND_TILE", 16)
            engine = _engine(model, params)
            sm = engine.state_manager
            shapes = {k: v.shape for k, v in sm.kv_cache.items()}
            logits = _served(engine, tokens)
            peak = dict(engine.last_put)
            engine.flush(7)
            out[name] = dict(
                logits=logits, totals=dict(engine.put_totals), shapes=shapes,
                last=peak, free=[g.allocator.free_blocks for g in sm.groups],
                total=[g.allocator.total_blocks for g in sm.groups],
                ids=(len(sm._free_id_slots), sm.id_slots))
    return out


@pytest.mark.parametrize("name", ["absorbed", "expanded"])
def test_chunks_then_decode_through_both_pools(tiny, paths, name):
    arch, *_, want, route, _ = tiny
    run = paths[name]
    # two groups, three leaves, none with a head axis: the whole-context
    # group's latent row and index key, the window group's wider row
    assert run["shapes"] == {"kv": (2, 128, 8, 128), "ki": (2, 128, 8, 16),
                             "kv1": (3, run["total"][1], 8, 128)}
    assert _worst(run["logits"], want) < 2e-6
    totals = run["totals"]
    assert totals["prefill_tokens"] == PROMPT
    absorbed = PROMPT + STEPS if name == "absorbed" else STEPS
    assert totals["latent_q_absorbed"] == totals["window_q_absorbed"] \
        == absorbed
    assert totals["latent_q_expanded"] == totals["window_q_expanded"] \
        == PROMPT + STEPS - absorbed
    if name == "expanded":
        # chunks of 32, 32 and 26 rebuild their contexts: whole, in tiles
        # of 16 (32 + 64 + 96), and in the window's tiles of 8 from the
        # tile of its first key (32; from 24 to 64; from 56 to 96)
        assert totals["latent_rows_expanded"] == 32 + 64 + 96
        assert totals["window_rows_expanded"] == 32 + 40 + 40
    # position p sees p + 1 keys and keeps 8 of them at most
    n = PROMPT + STEPS
    assert totals["sparse_keys_live"] == n * (n + 1) // 2
    assert totals["sparse_keys_selected"] == 36 + (n - 8) * 8


def test_absorbed_and_expanded_forms_agree(paths, tiny):
    want = tiny[4]
    assert np.abs(paths["absorbed"]["logits"] - paths["expanded"]["logits"]
                  ).max() < 2e-6 * (want.max() - want.min())


@pytest.mark.parametrize("name", ["absorbed", "expanded"])
def test_the_window_groups_blocks_come_back_and_all_are_free_at_the_end(
        paths, name):
    run = paths[name]
    # 96 positions are 12 blocks of 8; behind a window of 5 at most two
    # are live between puts, so ten and more went back while it lived
    assert run["totals"]["kv_blocks_released"] >= 10
    assert run["last"]["kv_g1_window"] == 5 and run["last"]["kv_g1_in_use"] <= 2
    assert run["last"]["kv_g0_window"] == 0 and run["last"]["kv_g0_in_use"] == 12
    assert run["free"] == run["total"]
    assert run["ids"][0] == run["ids"][1]


def test_the_selected_sets_are_the_references_wherever_the_edge_is_clear(
        tiny):
    """The leading layer's selection on the embedding: the program's
    functions (``index_qk``, the scores over a paged index pool, the
    exact top-k as indices and as a mask) against the reference's mask,
    at every position whose margin is above ``SELECT_EPS``."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models import hybrid
    from deepspeed_tpu.models.transformer import apply_rope, rope_table
    from deepspeed_tpu.ops import latent_attention as la

    arch, model, params, tokens, *_ = tiny
    b, cfg = block(), model.cfg
    toks = jnp.asarray(tokens, jnp.int32)
    T = len(tokens)
    want, margin = map(np.asarray, b.selected_keys(params, toks, arch,
                                                   q_block=16))
    clear = margin > b.SELECT_EPS
    assert clear.sum() > 0.9 * T
    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda a: a[0], params["layers"]["lead0"])
        h1 = hybrid.block_norm(cfg, params["embed"]["wte"][toks][None],
                               lp["attn_norm_w"])
        cos, sin = rope_table(cfg.max_seq_len, cfg.rot_dim, cfg.rope_theta)
        rope = lambda t: apply_rope(t, cos[:T], sin[:T],        # noqa: E731
                                    cfg.rope_interleaved)
        qi, ki, wi = hybrid.index_qk(cfg, h1, hybrid.latent_cq(cfg, h1, lp),
                                     lp, rope)
        live = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
        dense = np.asarray(hybrid.index_keep(
            hybrid.index_scores(qi, ki, wi), live[None], cfg.index_topk))[0]
        # the same keys in a paged pool, scored a query position a row
        bs = 8
        blocks = -(-T // bs)
        pool = jnp.zeros((1, blocks + 3, bs, cfg.index_head_dim))
        table = jnp.arange(blocks)[::-1] + 2            # not in order
        pool = pool.at[0, table].set(jnp.pad(
            ki[0], ((0, blocks * bs - T), (0, 0))).reshape(blocks, bs, -1))
        scores = la.index_score(
            qi[0].reshape(T, -1, cfg.index_head_dim), wi[0], pool, 0,
            jnp.broadcast_to(table, (T, blocks)), jnp.arange(T) + 1)[:, 0]
        idx, n = hybrid.index_select(
            scores, jnp.arange(blocks * bs)[None, :] <= jnp.arange(T)[:, None],
            cfg.index_topk)
    assert (dense[clear] == want[clear]).all()
    assert (dense[~clear] != want[~clear]).sum() <= 2 * (~clear).sum()
    idx, n = np.asarray(idx), np.asarray(n)
    assert (n == np.minimum(np.arange(T) + 1, cfg.index_topk)).all()
    for t in np.flatnonzero(clear):
        assert set(idx[t, :n[t]]) == set(np.flatnonzero(want[t]))


def test_a_sparse_layer_that_attended_unselected_keys_fails(tiny):
    """The same weights served with the selection switched off (a top-k
    as long as the context: every live key kept) against the reference,
    which keeps 8: the comparison that passes at 2e-6 fails by orders —
    the unselected keys carry weight, and a path that read them "because
    the result is within tolerance" would be seen."""
    import jax.numpy as jnp

    from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig

    arch, _, params, tokens, want, *_ = tiny
    everything = CausalLM(TransformerConfig(**dict(
        arch, dtype=jnp.float32, index_topk=256)))
    got = _served(_engine(everything, params), tokens)
    assert _worst(got, want) > 1e-3


def test_each_refused_feature_raises_its_own_error_and_the_rest_works(tiny):
    from deepspeed_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2, RaggedInferenceEngineConfig)
    from deepspeed_tpu.models.hybrid import (LatentKVUnsupported,
                                             RecurrentStateUnsupported,
                                             ReleasedKVUnsupported)

    arch, model, params, tokens, *_ = tiny
    sizing = dict(twin()["engine"], compile_ahead=0)

    def build(**more):
        return InferenceEngineV2(model, params=params,
                                 config=RaggedInferenceEngineConfig(
                                     **dict(sizing, **more)))

    # K/V by kv-head: quantized pools (a scale a head) and the KV tier
    with pytest.raises(LatentKVUnsupported):
        build(kv_quant_enabled=True)
    with pytest.raises(LatentKVUnsupported):
        build(enable_prefix_cache=True, kv_tier_enabled=True)
    # the whole context resident in one pool: the prefix cache
    with pytest.raises(ReleasedKVUnsupported):
        build(enable_prefix_cache=True)
    engine = build()
    with pytest.raises(ReleasedKVUnsupported):
        engine.configure_kv_quant(True)
    engine.put([1], [tokens[:32]])
    # a rollback past a released block, an export of a released context
    with pytest.raises(ReleasedKVUnsupported):
        engine.trim_sequence(1, 20)
    with pytest.raises(ReleasedKVUnsupported):
        engine.state_manager.export_sequence(1)
    # speculative verification rolls back too
    with pytest.raises(RecurrentStateUnsupported):
        engine.put([2], [tokens[:8]], verify_width=4)
    # and beside them the engine serves: the sequence goes on, the other
    # one starts, both give every block back
    engine.put([1], [tokens[32:50]])
    engine.put([1, 3], [[tokens[50]], tokens[:20]])
    for uid in (1, 2, 3):
        engine.flush(uid)
    sm = engine.state_manager
    assert [g.allocator.free_blocks for g in sm.groups] \
        == [g.allocator.total_blocks for g in sm.groups]


def test_the_four_shares_and_the_shared_expert_once_add_up_to_the_layer(
        tiny):
    """The share is the model (the guide's section 4): routed over all 16
    experts under the selection bias, each share's routed part over its
    own four, summed over the four shares, plus the shared expert once,
    is the uncut layer's FFN — the reference's and the program's
    (``moe_ffn`` whole, and one share of it against the reference's)."""
    import jax
    import jax.numpy as jnp

    from benchmark.model import seeded_params
    from deepspeed_tpu.models import hybrid
    from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig

    arch = dict(tiny[0], moe_held_experts=None)
    cfg = TransformerConfig(**dict(arch, dtype=jnp.float32))
    params = seeded_params(CausalLM(cfg), 3, jnp.float32)
    b = block()
    lp = jax.tree.map(lambda a: a[0], params["layers"]["slot0"])
    lp = dict(lp, router_b=0.05 * jax.random.normal(
        jax.random.PRNGKey(2), lp["router_b"].shape))
    h = jax.random.normal(jax.random.PRNGKey(4), (50, arch["hidden_size"]))
    E, n = arch["moe_num_experts"], 4
    with jax.default_matmul_precision("highest"):
        whole = b.routed_part(h, lp, arch, held=(0, E)) + b.shared_part(h, lp)
        uncut = hybrid.moe_ffn(cfg, h[None], lp)[0][0]
        parts = []
        for lo in range(0, E, n):
            share = dict(lp, **{k: lp[k][lo:lo + n]
                                for k in ("w_in", "w_gate", "w_out")})
            parts.append(b.routed_part(h, share, arch, held=(lo, n)))
        # one share through the program's layer: its routed part and the
        # shared expert, which every share carries and the sum counts once
        held = TransformerConfig(**dict(arch, dtype=jnp.float32,
                                        moe_held_experts=(n, n)))
        program = hybrid.moe_ffn(held, h[None], dict(lp, **{
            k: lp[k][n:2 * n] for k in ("w_in", "w_gate", "w_out")}))[0][0]
        shared = b.shared_part(h, lp)
    assert np.allclose(uncut, whole, atol=1e-5)
    assert np.allclose(sum(parts) + shared, whole, atol=1e-5)
    assert np.allclose(program, parts[1] + shared, atol=1e-5)
    assert all(float(jnp.abs(p).max()) > 0 for p in parts)


# ------------------------------------------------------ scopes and readers

def test_the_new_scope_names_resolve_through_the_blocks_scopes():
    b = block()
    body = "jit(_forward)/layers/while/body/closed_call/"
    cases = {
        body + "latent_attn/index/index_proj/dot_general:": "index_proj",
        body + "latent_attn/index/while/body/closed_call/index_score/"
               "index_score/pallas_call:": "index_score",
        body + "latent_attn/index/while/body/closed_call/index_select/"
               "top_k:": "index_select",
        body + "latent_attn/index/concatenate:": "index",
        body + "latent_attn/kv_write/index_write/scatter:": "index_write",
        body + "latent_attn/attend/mla_sparse_decode/pallas_call:": "attend",
        body + "latent_attn/attend/gather:": "attend",
        body + "window_latent_attn/attend/mla_window_decode/pallas_call:":
            "attend",
        body + "window_latent_attn/while/body/kv_expand/dot_general:":
            "kv_expand",
        body + "window_latent_attn/qkv/dot_general:": "qkv",
        body + "window_latent_attn/mul:": "window_latent_attn",
        "jit(_forward)/layers/latent_attn/qkv/dot_general:": "qkv",
        "jit(_forward)/layers/mlp/dense_mlp/dot_general:": "dense_mlp",
        body + "mlp/experts/jit(gmm)/pallas_call:": "experts",
    }
    for op_name, want in cases.items():
        assert scopes.scope_of(op_name, b.SCOPES) == want, op_name
    assert "window_latent_attn" in scopes.scope_path(
        body + "window_latent_attn/while/body/kv_expand/dot_general:",
        b.SCOPES)
    assert set(b.ATTN_SCOPES.values()) | set(b.INDEX_SCOPES) < set(b.SCOPES)


class _Ctx:
    """A hand-made context: the block, the program's ``forward`` spans,
    kernel seconds of a trace."""

    def __init__(self, records, kernel_seconds=None, traced=True):
        _, info = real()
        self.info = info
        self.device = {"kind": "TPU v5 lite"}
        self.result = {
            "arch": info["config"]["transformer_config"],
            "window": (0.0, 100.0), "trace_marks": (0.0, 100.0),
            "program_spans": [{"name": "forward", "t_start": float(i),
                               "attrs": r} for i, r in enumerate(records)]}
        self.trace = {"kernel_seconds": kernel_seconds or {}} \
            if traced else None


def _record(**over):
    base = {"valid_tokens": 0, "kv_read_tokens": 0, "qk_pairs": 0,
            "latent_q_absorbed": 0, "latent_keys_absorbed": 0,
            "latent_pairs_absorbed": 0, "latent_q_expanded": 0,
            "latent_rows_expanded": 0, "prefill_tokens": 0,
            "sparse_keys_live": 0, "sparse_keys_selected": 0,
            "sparse_keys_absorbed": 0, "window_q_absorbed": 0,
            "window_q_expanded": 0, "window_rows_expanded": 0,
            "window_keys_absorbed": 0, "window_pairs_absorbed": 0,
            "kv_g1_read_tokens": 0, "kv_g1_qk_pairs": 0}
    return dict(base, **over)


def test_the_new_readers_on_hand_made_contexts():
    from benchmark import peaks

    b = block()
    arch = real()[1]["config"]["transformer_config"]
    chunk = _record(valid_tokens=2048, kv_read_tokens=16384,
                    qk_pairs=2048 * 14336 + 2048 * 2049 // 2,
                    latent_q_expanded=2048, prefill_tokens=2048,
                    sparse_keys_live=2048 * 14336 + 2048 * 2049 // 2,
                    sparse_keys_selected=2048 * 2048,
                    window_q_expanded=2048, kv_g1_read_tokens=2560,
                    kv_g1_qk_pairs=2048 * 513)
    step = _record(valid_tokens=4, kv_read_tokens=40000, qk_pairs=40000,
                   latent_q_absorbed=4, latent_keys_absorbed=40000,
                   latent_pairs_absorbed=40000, sparse_keys_live=40000,
                   sparse_keys_selected=4 * 2048,
                   sparse_keys_absorbed=4 * 2048, window_q_absorbed=4,
                   window_keys_absorbed=4 * 513, window_pairs_absorbed=4 * 513,
                   kv_g1_read_tokens=4 * 513, kv_g1_qk_pairs=4 * 513)
    last = _record(valid_tokens=1)      # still running: left out
    ctx = _Ctx([chunk, step, last], {
        "kernel:index_score": 0.010, "kernel:mla_sparse_decode": 0.001,
        "kernel:mla_window_decode": 0.001, "kernel:mla_window_prefill": 0.004})
    assert sparse_readers.select_ratio(ctx) == pytest.approx(
        (2048 * 2048 + 4 * 2048) / (chunk["qk_pairs"] + 40000))
    least = lambda cost: peaks.roofline_seconds(cost, "TPU v5 lite")  # noqa
    assert sparse_readers.index_score_roofline(ctx) == pytest.approx(
        100 * 2 * (least(b.index_score_cost(arch, 2048, 16384,
                                            chunk["qk_pairs"]))
                   + least(b.index_score_cost(arch, 4, 40000, 40000)))
        / 0.010)
    assert sparse_readers.sparse_attention_roofline(ctx) == pytest.approx(
        100 * 2 * least(b.mla_sparse_decode_cost(arch, 4, 4 * 2048)) / 0.001)
    assert sparse_readers.window_roofline(ctx) == pytest.approx(
        100 * 3 * (least(b.mla_window_cost(arch, 0, 0, 0, 2048, 2560,
                                           2048 * 513))
                   + least(b.mla_window_cost(arch, 4, 4 * 513, 4 * 513,
                                             0, 0, 0))) / 0.005)
    assert 0 < sparse_readers.index_score_roofline(ctx) < 100
    # nothing to read: an untraced run, the parent's spans, no kernel
    assert sparse_readers.index_score_roofline(
        _Ctx([chunk, step], traced=False)) is None
    parent = {k: v for k, v in chunk.items()
              if not k.startswith(("sparse_", "window_"))}
    assert sparse_readers.select_ratio(_Ctx([parent, parent])) is None
    assert sparse_readers.window_roofline(_Ctx([parent, parent, parent], {
        "kernel:mla_window_decode": 0.001})) is None
    assert sparse_readers.sparse_attention_roofline(
        _Ctx([chunk, step, last])) is None


# ----------------------------------------------------------- the rehearsal

@pytest.mark.parametrize("traced", [1])
def test_the_cell_rehearsed_on_the_cpu(checkout, capsys, traced,  # noqa: F811
                                       monkeypatch):
    """The whole runner over the engine at the tiny twin's size, under
    the real names (the twin and its mix reach the checkout through
    ``tests/conftest.py``, found by name): prompts in several chunks
    beside decoding rows, both pools, the logits check against this
    block's reference, every block of both groups back. Traced only:
    the untraced line is the harness's own, held by the other cells'
    rehearsals."""
    path = os.path.join(checkout, "benchmark/workloads", CELL + ".json")
    _write(path, dict(_read(path), rate_rps=10.0, trace_s=1.0))
    manifest = mf.load(checkout)
    mf.validate(manifest, checkout)
    info = mf.resolve(manifest, CELL, checkout)
    assert info["config"] == twin()
    assert info["traffic"] == _read(os.path.join(TWINS, "traffic",
                                                 "longctx.json"))
    group = "per_layer" if traced else "end_to_end"
    if traced:
        from deepspeed_tpu.ops import latent_attention as la

        monkeypatch.setattr(la, "ABSORB_MAX_QUERIES", 8)
        monkeypatch.setattr(la, "absorb_max_queries", lambda *a, **k: 8)
        monkeypatch.setattr(la, "EXPAND_TILE", 16)
    line, extra = rehearse(checkout, capsys, CELL, traced)
    assert line["correct"], extra["why_not"]
    assert line["failed"] == 0 and line["attempted"] > 5
    check_line(line, manifest, CELL, group)
    check = extra["counters"]["logits_check"]
    assert 0 < check["max_rel_err"] < 1e-4 and check["rms_rel_err"] < 1e-4
    if traced:
        # off the chip the counters are read, the device is not
        assert {"sparse_select_ratio", "kv_expand_ratio",
                "kv_window_blocks_peak_share", "kv_full_blocks_peak_share",
                "kv_resident_ratio"} <= set(line["metrics"])
        # prompts of 24-160 under a selection of 8: a sixth and less
        assert 0.05 < line["metrics"]["sparse_select_ratio"]["value"] < 0.5
        assert line["metrics"]["kv_resident_ratio"]["value"] < 1
        assert not {"sparse_index_share", "index_score_roofline",
                    "mla_window_roofline"} & set(line["metrics"])
    else:
        assert {"ttft_p90_ms", "setup_s"} <= set(line["metrics"])
