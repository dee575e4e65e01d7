"""A hybrid model (Gated DeltaNet layers beside gated attention, a sparse
FFN with a held share of the experts) through ``InferenceEngineV2``: the
recurrent state lives in slots beside the paged K/V pool. A tiny
configuration of the published shape — two periods of linear x 3 + full,
a head size that is not hidden/heads, 8 experts top-2 of which 4 are
held, a shared expert — in float32 on the CPU."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.engine_v2 import (
    InferenceEngineV2, RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.scheduling_utils import SchedulingResult
from deepspeed_tpu.inference.v2.testing import share_forward
from deepspeed_tpu.models.hybrid import RecurrentStateUnsupported
from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig

CFG = TransformerConfig(
    vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=8,
    num_heads=4, num_kv_heads=2, head_size=16, max_seq_len=256,
    norm="rmsnorm", norm_eps=1e-6, norm_zero_centered=True,
    activation="silu", position="rope", rope_pct=0.25, rope_theta=1e7,
    tie_embeddings=False, dtype=jnp.float32,
    layer_pattern=("linear", "linear", "linear", "full"),
    attn_output_gate=True, qk_norm=True,
    linear_num_key_heads=2, linear_num_value_heads=4,
    linear_key_head_dim=8, linear_value_head_dim=8, linear_conv_kernel=4,
    moe_num_experts=8, moe_top_k=2, moe_dropless=True, moe_norm_topk=True,
    moe_held_experts=(2, 4), moe_intermediate_size=16,
    moe_shared_intermediate_size=16)
SIZING = dict(max_ragged_batch_size=64, max_ragged_sequence_count=4,
              max_chunk_tokens=16, kv_blocks=64, kv_block_size=8)


@pytest.fixture(scope="module")
def model_and_params():
    model = CausalLM(CFG)
    params = model.init(jax.random.PRNGKey(0))
    # gains off their initial values, so a dropped gain would show
    flat, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(1), len(flat))
    flat = [leaf + 0.05 * jax.random.normal(k, leaf.shape)
            if "norm" in jax.tree_util.keystr(path) else leaf
            for (path, leaf), k in zip(flat, keys)]
    return model, jax.tree_util.tree_unflatten(tree, flat)


#: the engines of one sizing serve one model at one configuration: they
#: share one jitted forward (``testing.share_forward``), so a case
#: compiles only the buckets no earlier case ran
_FORWARDS = {}


def engine(model_and_params, own_forward=False, **sizing):
    model, params = model_and_params
    eng = InferenceEngineV2(model, params=params,
                            config=RaggedInferenceEngineConfig(
                                **dict(SIZING, **sizing)))
    if own_forward:
        return eng
    return share_forward(eng, _FORWARDS,
                         (id(model), tuple(sorted(sizing.items()))))


def prompt(seed, n):
    return np.random.default_rng(seed).integers(0, 128, size=n).tolist()


def feed(eng, uid, tokens, chunk=16):
    """Prefill in chunks; the logits after the last token."""
    for at in range(0, len(tokens), chunk):
        out = eng.put([uid], [tokens[at:at + chunk]])
    return np.asarray(out[0])


def decode(eng, uid, tokens, steps, chunk=16):
    """Prefill then greedy decode: (logits of every step, all tokens)."""
    got, tokens = [feed(eng, uid, tokens, chunk)], list(tokens)
    for _ in range(steps):
        tokens.append(int(np.argmax(got[-1])))
        got.append(np.asarray(eng.put([uid], [[tokens[-1]]])[0]))
    return got, tokens


def reference_block():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from benchmark import manifest as mf

    return mf.find_module(mf.HERE, "blocks", "qwen3_next")


@pytest.mark.parametrize("n,chunk", [(45, 16), (16, 16), (37, 8), (5, 16),
                                     (70, 16)])
def test_chunks_then_decode_agree_with_apply_and_the_reference(
        model_and_params, n, chunk):
    model, params = model_and_params
    eng = engine(model_and_params)
    got, tokens = decode(eng, 7, prompt(n, n), steps=3, chunk=chunk)
    ids = jnp.asarray(tokens)
    want = np.asarray(model.apply(params, ids[None]))[0]
    arch = {f.name: getattr(CFG, f.name) for f in dataclasses.fields(CFG)}
    ref = np.asarray(reference_block().logits(params, ids, arch, q_block=32))
    for step, g in enumerate(got):
        for other in (want, ref):
            w = other[n - 1 + step]
            assert np.abs(g - w).max() < 1e-4 * np.abs(w).max()


def test_a_sequence_does_not_depend_on_its_neighbours_or_on_padding(
        model_and_params):
    """The same sequence alone ([1, C] buckets) and beside three others of
    other lengths ([4, C] buckets, its rows padded): the same logits, so
    no state leaks between slots and padded positions change none."""
    mine = prompt(1, 29)
    alone, _ = decode(engine(model_and_params), 1, mine, steps=2)
    eng = engine(model_and_params)
    others = {2: prompt(2, 40), 3: prompt(3, 7), 4: prompt(4, 16)}
    fed = {u: 0 for u in (1, 2, 3, 4)}
    everyone = {1: mine, **others}
    last = None
    while any(fed[u] < len(everyone[u]) for u in everyone):
        uids = [u for u in everyone if fed[u] < len(everyone[u])]
        chunks = [everyone[u][fed[u]:fed[u] + 16] for u in uids]
        out = eng.put(uids, chunks)
        for i, u in enumerate(uids):
            fed[u] += len(chunks[i])
            if u == 1 and fed[1] == len(mine):
                last = np.asarray(out[i])
    together = [last]
    toks = list(mine)
    for _ in range(2):
        toks.append(int(np.argmax(together[-1])))
        out = eng.put([4, 1, 2], [[5], [toks[-1]], [9]])
        together.append(np.asarray(out[1]))
    for a, b in zip(alone, together):
        np.testing.assert_allclose(b, a, atol=2e-6)


def test_flush_returns_slot_and_blocks_and_a_reused_slot_starts_from_zero(
        model_and_params):
    eng = engine(model_and_params)
    sm = eng.state_manager
    first = feed(eng, 10, prompt(10, 33))
    occ = eng.occupancy()
    assert occ["state_slots"] == 4 and occ["state_slots_used"] == 1
    assert occ["state_bytes"] == sum(int(x.nbytes)
                                     for x in sm.state_cache.values())
    assert eng.last_put["state_slots_used"] == 1
    slot = sm.get_sequence(10).state_slot
    eng.flush(10)
    assert eng.occupancy()["state_slots_used"] == 0
    assert sm.available_blocks == SIZING["kv_blocks"]
    # the slot is not cleared on the device: the next sequence to take it
    # starts from zero all the same
    assert float(jnp.abs(sm.state_cache["ssm"][:, slot]).max()) > 0
    again = feed(eng, 11, prompt(10, 33))
    assert sm.get_sequence(11).state_slot == slot
    np.testing.assert_allclose(again, first, atol=1e-6)
    eng.flush(11)
    assert sm.free_state_slots == 4


def test_the_pool_holds_the_attention_layers_only(model_and_params):
    eng = engine(model_and_params)
    sm = eng.state_manager
    assert CFG.num_attn_layers == 2 and CFG.num_linear_layers == 6
    assert sm.kv_cache["k"].shape == (2, 64, 2, 8, 16)
    assert sm.state_cache["ssm"].shape == (6, 5, 4, 8, 8)
    assert sm.state_cache["ssm"].dtype == jnp.float32
    assert sm.state_cache["conv"].shape == (6, 5, 3, 2 * 2 * 8 + 4 * 8)
    # 2 (k, v) x 2 layers x 2 heads x 8 slots x 16 x 4 B
    assert eng.occupancy()["bytes_per_block"] == 2 * 2 * 2 * 8 * 16 * 4
    assert set(sm.forward_cache) == {"k", "v", "ssm", "conv"}


def test_no_slot_no_admission(model_and_params):
    eng = engine(model_and_params)
    for uid in range(4):
        eng.put([uid], [prompt(uid, 3)])
    assert eng.can_schedule([0, 1], [1, 1]) == SchedulingResult.Success
    assert eng.can_schedule([9], [4]) == SchedulingResult.KVCacheLimitExceeded
    eng.flush(2)
    assert eng.can_schedule([9], [4]) == SchedulingResult.Success


@pytest.mark.parametrize("chunked", [0, 2])
def test_export_then_import_reproduces_the_next_logits(model_and_params,
                                                       chunked):
    src = engine(model_and_params)
    tokens = prompt(20, 41)
    got, all_tokens = decode(src, 5, tokens, steps=2)
    payload = src.export_sequence(5, chunk_blocks=chunked)
    assert set(payload["state"]) == {"ssm", "conv"}
    nxt = int(np.argmax(got[-1]))
    want = np.asarray(src.put([5], [[nxt]])[0])
    dst = engine(model_and_params)
    dst.put([77], [prompt(3, 9)])           # its slot 0 is taken
    dst.import_sequence(6, payload, all_tokens)
    np.testing.assert_allclose(np.asarray(dst.put([6], [[nxt]])[0]), want,
                               atol=1e-6)
    # a payload without the state, or of another model, is refused whole
    bare = {k: v for k, v in payload.items() if k != "state"}
    with pytest.raises(ValueError, match="recurrent-state"):
        dst.import_sequence(8, bare, all_tokens)
    assert dst.state_manager.get_sequence(8) is None
    assert dst.occupancy()["state_slots_used"] == 2


def test_the_preemption_stash_carries_the_state(model_and_params):
    eng = engine(model_and_params)
    tokens = prompt(30, 26)
    got, all_tokens = decode(eng, 5, tokens, steps=1)
    nxt = int(np.argmax(got[-1]))
    eng.preempt_stash(5, eng.export_sequence(5))
    eng.flush(5)
    assert eng.occupancy()["state_slots_used"] == 0
    feed(eng, 50, prompt(31, 30))           # someone else takes the slot
    eng.import_sequence(5, eng.preempt_restore_payload(5), all_tokens)
    resumed = np.asarray(eng.put([5], [[nxt]])[0])
    fresh = engine(model_and_params)
    feed(fresh, 1, all_tokens)
    np.testing.assert_allclose(
        resumed, np.asarray(fresh.put([1], [[nxt]])[0]), atol=2e-6)


def test_features_that_assume_per_token_kv_raise_the_typed_error(
        model_and_params, devices8):
    model, params = model_and_params
    eng = engine(model_and_params)
    feed(eng, 1, prompt(1, 20))
    with pytest.raises(RecurrentStateUnsupported, match="trim_sequence"):
        eng.trim_sequence(1, 2)
    assert eng.trim_sequence(1, 0) == 0     # nothing to roll back: no-op
    with pytest.raises(RecurrentStateUnsupported, match="prefix cache"):
        eng.configure_prefix_cache(True)
    assert eng.match_prefix(2, prompt(1, 20)) == 0      # off: untouched
    with pytest.raises(RecurrentStateUnsupported, match="prefix cache"):
        engine(model_and_params, enable_prefix_cache=True)
    with pytest.raises(RecurrentStateUnsupported, match="KV tier"):
        eng.configure_kv_tier(True)
    with pytest.raises(RecurrentStateUnsupported, match="verif"):
        eng.put([1], [[3, 4]], verify_width=2)
    from jax.sharding import Mesh

    mesh = Mesh(np.array(devices8[:2]), ("tensor",))
    with pytest.raises(RecurrentStateUnsupported, match="TP serving"):
        InferenceEngineV2(model, params=params, mesh=mesh,
                          config=RaggedInferenceEngineConfig(**SIZING))
    assert issubclass(RecurrentStateUnsupported, NotImplementedError)


@pytest.mark.parametrize("call", ["init_cache", "prefill", "decode_step",
                                  "init_paged_cache", "prefill_paged",
                                  "decode_step_paged"])
def test_the_contiguous_cache_paths_raise_for_a_hybrid_model(
        model_and_params, call):
    model, params = model_and_params
    tok = jnp.zeros((1, 4), jnp.int32)
    cache = {"k": jnp.zeros((8, 1, 8, 2, 16)), "v": jnp.zeros((8, 1, 8, 2, 16))}
    args = {"init_cache": (1, 8), "prefill": (params, tok, cache),
            "decode_step": (params, cache, tok[:, 0], 0),
            "init_paged_cache": (1, 8),
            "prefill_paged": (params, tok, jnp.array([4]), cache, None),
            "decode_step_paged": (params, cache, None, tok[:, 0],
                                  jnp.array([0]))}[call]
    with pytest.raises(NotImplementedError, match="InferenceEngineV2"):
        getattr(model, call)(*args)


def test_the_puts_counters(model_and_params):
    eng = engine(model_and_params)
    eng.put([1, 2], [prompt(1, 10), prompt(2, 3)])
    # 13 valid tokens x top-2 x 8 layers routed; half the experts held
    assert eng.last_put["moe_rows_routed"] == 13 * 2 * 8
    assert eng.last_put["moe_rows_held"] == 13 * 8
    eng.put([1], [[5]])
    assert eng.put_totals["moe_rows_routed"] == 14 * 2 * 8
    assert eng.put_totals["moe_rows_held"] == 14 * 8
    assert eng.last_put["state_slots_used"] == 2


def test_a_dense_model_is_given_no_slots(model_and_params):
    from deepspeed_tpu.models.transformer import TINY_TEST

    model = CausalLM(TINY_TEST)
    eng = InferenceEngineV2(model, config=RaggedInferenceEngineConfig(
        **SIZING))
    eng.put([1], [[1, 2, 3]])
    assert eng.state_manager.state_cache == {}
    assert set(eng.state_manager.forward_cache) == {"k", "v"}
    assert "state_slots_used" not in eng.last_put
    assert set(eng.put_totals) == {"forwards", "positions_computed",
                                   "tokens_valid", "puts_split",
                                   "forwards_qkv_fused", "forwards_merged"}
    occ = eng.occupancy()
    assert occ["state_slots"] == occ["state_slots_used"] == 0
    assert eng.state_manager.get_sequence(1).state_slot == -1


# ------------------------------------------------- buckets, compiling ahead

def test_a_chunk_is_padded_to_the_delta_rules_tile_and_no_narrower(
        model_and_params):
    """The forward's shapes: ``[1, C]`` from the tile (here the chunk cap,
    which is under it) up, and ``[S, 1]``; a dense model keeps every power
    of two."""
    from deepspeed_tpu.inference.v2.ragged import RaggedBatchWrapper
    from deepspeed_tpu.ops import gated_delta

    eng = engine(model_and_params)
    assert eng.forward_shapes() == [(1, 1), (1, 16), (2, 1), (4, 1)]
    eng.put([1], [prompt(0, 3)])
    assert eng.last_put["bucket_chunk"] == 16
    eng.put([1], [[5]])
    assert eng.last_put["bucket_chunk"] == 1
    wide = RaggedBatchWrapper(32, 1024, 8, min_chunk=gated_delta.TILE)
    assert wide.buckets() == ([1, 2, 4, 8, 16, 32],
                              [1, 64, 128, 256, 512, 1024])
    assert RaggedBatchWrapper(4, 16, 8).buckets() == ([1, 2, 4],
                                                      [1, 2, 4, 8, 16])


def test_compiled_ahead_the_engine_gives_the_same_logits_and_compiles_no_more(
        model_and_params):
    """``compile_ahead``: every shape's executable is there when the
    engine is built, a put runs it (nothing is compiled at first use), and
    the logits are those of the engine that compiles at first use."""
    lazy, ahead = engine(model_and_params, own_forward=True), engine(
        model_and_params, compile_ahead=2)
    p, q = prompt(3, 21), prompt(4, 5)
    puts = [([1], [p[:16]]), ([1, 2], [p[16:], q]), ([1, 2], [[7], [9]])]
    for uids, tokens in puts:
        np.testing.assert_allclose(np.asarray(ahead.put(uids, tokens)),
                                   np.asarray(lazy.put(uids, tokens)),
                                   rtol=1e-5, atol=1e-5)
    # [1, 16] (each chunk row is a forward of its own) and [2, 1]: traced
    # at first use by the one, never by the other
    assert lazy.paged.forward._cache_size() == 2
    assert ahead._forward_jit._cache_size() == 0
    for eng in (lazy, ahead):
        for uid in (1, 2):
            eng.flush(uid)
        assert eng.occupancy()["state_slots_used"] == 0


# ---------------------------------------------- a router that reads early

#: a block whose router reads the layer's own input, ahead of the mixer
#: (``moe_router_input`` "layer"), over gated-ReLU experts all held here:
#: one unrotated whole-context layer and three rotated window layers a
#: period, 7 query heads a K/V head
EARLY = TransformerConfig(
    vocab_size=128, hidden_size=32, intermediate_size=24, num_layers=8,
    num_heads=14, num_kv_heads=2, head_size=8, max_seq_len=128,
    norm="rmsnorm", norm_eps=1e-6, activation="silu", position="rope",
    rope_theta=1.5e6, tie_embeddings=False, dtype=jnp.float32,
    layer_pattern=("full", "window", "window", "window"), sliding_window=16,
    rope_kinds=("window",), moe_num_experts=8, moe_top_k=3,
    moe_dropless=True, moe_norm_topk=True, moe_held_experts=(0, 8),
    moe_intermediate_size=24, moe_activation="reglu",
    moe_router_input="layer")


@pytest.fixture(scope="module")
def early_model_and_params():
    model = CausalLM(EARLY)
    params = model.init(jax.random.PRNGKey(2))
    # projections loud enough that where the router reads shows
    layers = {slot: {name: a if name.endswith("norm_w") else 4.0 * a
                     for name, a in lp.items()}
              for slot, lp in params["layers"].items()}
    return model, dict(params, layers=layers)


@pytest.mark.parametrize("n,chunk", [(45, 16), (37, 8)])
def test_an_early_router_through_the_engine_agrees_with_apply(
        early_model_and_params, n, chunk):
    """Prefill in chunks across the window's edge, then decode through
    both layer groups' pools: the logits ``CausalLM.apply`` gives, whose
    ``run_period`` takes the router's logits from the layer's input too."""
    model, params = early_model_and_params
    eng = InferenceEngineV2(model, params=params,
                            config=RaggedInferenceEngineConfig(**SIZING))
    got, tokens = decode(eng, 7, prompt(n, n), steps=3, chunk=chunk)
    want = np.asarray(model.apply(params, jnp.asarray(tokens)[None]))[0]
    for step, g in enumerate(got):
        w = want[n - 1 + step]
        assert np.abs(g - w).max() < 1e-4 * np.abs(w).max()
    assert eng.last_put["moe_rows_held"] == eng.last_put["moe_rows_routed"] \
        == 3 * 8
    eng.flush(7)
    assert all(g.allocator.free_blocks == g.allocator.total_blocks
               for g in eng.state_manager.groups)


def test_where_the_router_reads_is_the_models(early_model_and_params):
    """The same weights under the default — the router on the FFN's own
    normed input — are another model; the field is no leaf of the tree."""
    model, params = early_model_and_params
    late = CausalLM(dataclasses.replace(EARLY, moe_router_input="ffn"))
    assert jax.tree.structure(late.init(jax.random.PRNGKey(2))) \
        == jax.tree.structure(params)
    tokens = jnp.asarray(prompt(5, 40))[None]
    a, b = (np.asarray(m.apply(params, tokens))[0] for m in (model, late))
    assert np.abs(a - b).max() > 1e-3 * np.abs(a).max()


# ------------------------------------ experts indexed in their stacks

def _sliced_by_hand(real):
    """``dropless_moe_mlp`` as a scan that carried the expert leaves in
    its ``xs`` would call it: the period's experts cut out of their
    stacks (``stack[p]``) in front of the call, the call on them alone."""
    def mlp(tokens, logits, w_in, w_out, w_gate=None, *, period, held, **kw):
        n = logits.shape[-1] if held is None else held[1]

        def cut(w):
            return None if w is None else jax.lax.dynamic_slice_in_dim(
                w, period * n, n)
        return real(tokens, logits, cut(w_in), cut(w_out), cut(w_gate),
                    held=held, **kw)
    return mlp


@pytest.mark.parametrize("cfg", [
    pytest.param(CFG, id="a_held_share_gated"),
    pytest.param(dataclasses.replace(CFG, moe_activation="relu2"),
                 id="a_held_share_ungated"),
    pytest.param(EARLY, id="an_early_router_reglu"),
    pytest.param(dataclasses.replace(EARLY, moe_held_experts=None),
                 id="every_expert_no_held")])
def test_experts_indexed_in_their_stacks_are_the_periods_own(cfg,
                                                             monkeypatch):
    """Two periods deep the scan closes over the expert stacks and the
    grouped matmul indexes the period's experts in them
    (``hybrid.expert_stacks``): chunk ``[1, C]`` and decode ``[S, 1]``
    forwards, one row of the step padding, give bit for bit the logits and
    the cache of the same forwards with each period's leaves sliced out
    by hand."""
    from deepspeed_tpu.models import hybrid

    assert cfg.num_periods == 2
    model = CausalLM(cfg)
    params = model.init(jax.random.PRNGKey(3))
    p, q, r = prompt(11, 27), prompt(12, 5), prompt(13, 16)
    puts = [([1], [p[:16]]), ([1, 2, 3], [p[16:], q, r]),
            ([1, 2, 3], [[7], [9], [11]]), ([1, 3], [[5], [6]])]

    def run():
        eng = InferenceEngineV2(model, params=params,
                                config=RaggedInferenceEngineConfig(**SIZING))
        logits, buckets = [], []
        for uids, tokens in puts:
            logits.append(np.asarray(eng.put(uids, tokens)))
            buckets.append((eng.last_put["bucket_seqs"],
                            eng.last_put["bucket_chunk"]))
        # chunk rows short of their bucket, three rows in a step of four
        assert buckets == [(1, 16), (1, 16), (4, 1), (2, 1)]
        return logits, {k: np.asarray(v) for k, v in
                        eng.state_manager.forward_cache.items()}

    stacked = run()
    seen = []
    by_hand = _sliced_by_hand(hybrid.dropless_moe_mlp)
    monkeypatch.setattr(
        hybrid, "dropless_moe_mlp",
        lambda *a, **kw: seen.append(kw["period"]) or by_hand(*a, **kw))
    sliced = run()
    assert seen and all(s is not None for s in seen)
    for a, b in zip(stacked[0], sliced[0]):
        np.testing.assert_array_equal(a, b)
    assert stacked[1].keys() == sliced[1].keys()
    for leaf in stacked[1]:
        np.testing.assert_array_equal(stacked[1][leaf], sliced[1][leaf])


# ------------------------------------------- several runs of layers (SambaY)
#
# A decoder-hybrid-decoder at a tiny size: (mamba1, window) x 3, (mamba1,
# full) x 1, (gmu, cross) x 2 — LayerNorm with bias, differential attention
# with biases, no position term, a window of 16 (two blocks of 8).

RUNS_CFG = TransformerConfig(
    vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=12,
    num_heads=8, num_kv_heads=4, head_size=8, max_seq_len=256,
    sliding_window=16, norm="layernorm", norm_eps=1e-5, activation="silu",
    position="rope", rope_kinds=(), tie_embeddings=True, qkv_bias=True,
    o_bias=True, attn_scale=8 ** -0.5, diff_attn=True, dtype=jnp.float32,
    layer_runs=((("mamba1", "window"), 3), (("mamba1", "full"), 1),
                (("gmu", "cross"), 2)),
    mamba1_inner_size=64, mamba1_state_size=4, mamba1_dt_rank=4,
    mamba1_conv_kernel=4)
RUNS_ARCH = dict(
    vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=12,
    num_heads=8, num_kv_heads=4, head_size=8, sliding_window=16,
    norm_eps=1e-5, layer_runs=[[["mamba1", "window"], 3],
                               [["mamba1", "full"], 1],
                               [["gmu", "cross"], 2]],
    mamba1_inner_size=64, mamba1_state_size=4, mamba1_dt_rank=4,
    mamba1_conv_kernel=4)
RUNS_PROMPT, RUNS_STEPS = 70, 4
_RUNS_FORWARDS = {}


def runs_block():
    reference_block()       # puts the checkout on the path
    from benchmark import manifest as mf

    return mf.find_module(mf.HERE, "blocks", "phi4flash")


@pytest.fixture(scope="module")
def runs_model():
    """The model, weights with every gain and bias off its initial value
    (a dropped one would show), a prompt longer than window + block, and
    the reference's logits: every layer on every position."""
    model = CausalLM(RUNS_CFG)
    params = model.init(jax.random.PRNGKey(2))
    flat, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(3), len(flat))
    flat = [leaf + 0.05 * jax.random.normal(k, leaf.shape)
            if "norm" in jax.tree_util.keystr(path)
            or jax.tree_util.keystr(path).endswith("_b']") else leaf
            for (path, leaf), k in zip(flat, keys)]
    params = jax.tree_util.tree_unflatten(tree, flat)
    tokens = prompt(11, RUNS_PROMPT + RUNS_STEPS)
    want = np.asarray(runs_block().logits(
        params, np.asarray(tokens, np.int32), RUNS_ARCH, 16))[:len(tokens)]
    return model, params, tokens, want


def runs_engine(runs_model, params=None, dtype=None, **sizing):
    model, own = runs_model[:2]
    if dtype is not None:
        model = CausalLM(dataclasses.replace(RUNS_CFG, dtype=dtype))
    eng = InferenceEngineV2(
        model, params=own if params is None else params,
        config=RaggedInferenceEngineConfig(**dict(SIZING, **sizing)))
    if dtype is not None:
        return eng
    return share_forward(eng, _RUNS_FORWARDS,
                         tuple(sorted(sizing.items())))


def runs_served(eng, tokens, uid=7, chunk=16):
    """Prefill in chunks then feed the given tokens: the logits at the
    prompt's last position and at each later one."""
    got = [feed(eng, uid, tokens[:RUNS_PROMPT], chunk)]
    for t in tokens[RUNS_PROMPT:]:
        got.append(np.asarray(eng.put([uid], [[t]])[0]))
    return np.stack(got)


def runs_worst(got, want):
    return np.abs(got - want[RUNS_PROMPT - 1:]).max() \
        / (want.max() - want.min())


def test_runs_apply_agrees_with_the_reference_at_every_position(runs_model):
    model, params, tokens, want = runs_model
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(model.apply)(
            params, jnp.asarray(tokens)[None]))[0]
    assert np.abs(got - want).max() < 2e-6 * (want.max() - want.min())


@pytest.mark.parametrize("chunk", [16, 12])
def test_runs_chunks_then_decode_agree_with_the_whole_forward(runs_model,
                                                              chunk):
    """Prefill in several chunks, then decode through the pools and the
    slots, against the reference's forward of every layer on every
    position: a prompt of 70 crosses the window of 16 + a block of 8, so
    window blocks are handed back on the way. Float32 agrees to 2e-6 of
    range; the same engine in bfloat16 is a thousand times off."""
    _, _, tokens, want = runs_model
    eng = runs_engine(runs_model)
    assert runs_worst(runs_served(eng, tokens, chunk=chunk), want) < 5e-6
    totals = eng.put_totals
    assert totals["kv_blocks_released"] > 0
    assert totals["ssm_chunk_tokens"] == RUNS_PROMPT
    assert totals["ssm_rows_stepped"] == RUNS_STEPS
    sm = eng.state_manager
    # the pools hold what is WRITTEN: one whole-context layer, three
    # window layers, K/V heads joined in pairs; the cross layers none
    shapes = {k: v.shape for k, v in sm.forward_cache.items()}
    assert shapes["k"][0] == 1 and shapes["k1"][0] == 3
    assert shapes["k"][2:] == (2, 8, 16) == shapes["v1"][2:]
    assert shapes["mamba1_ssm"] == (4, 5, 4, 64)
    assert shapes["mamba1_conv"] == (4, 5, 3, 64)
    eng.flush(7)
    assert sm.allocator.free_blocks == sm.allocator.total_blocks
    assert sm.free_state_slots == sm.state_slots


def test_runs_in_bfloat16_fail_the_float32_limit(runs_model):
    _, params, tokens, want = runs_model
    low = runs_engine(runs_model, dtype=jnp.bfloat16, params=jax.tree.map(
        lambda a: a.astype(jnp.bfloat16), params))
    got = runs_served(low, tokens).astype(np.float32)
    assert runs_worst(got, want) > 1e-3


def test_the_exit_gives_the_whole_forwards_value_on_one_row(runs_model):
    """A chunk forward's logits at its last row equal the reference's of
    all layers on all positions there — every chunk's, not the last
    one's alone — and one position a row entered the layers behind the
    exit, not the chunk's width."""
    _, _, tokens, want = runs_model
    assert RUNS_CFG.exit_at() == (1, 1)
    eng = runs_engine(runs_model)
    span = want.max() - want.min()
    for at in range(0, 64, 16):
        out = np.asarray(eng.put([3], [tokens[at:at + 16]])[0])
        assert np.abs(out - want[at + 15]).max() < 5e-6 * span
        assert eng.last_put["xdec_rows"] == 1
        assert eng.last_put["valid_tokens"] == 16
        # seven... here two cross layers' walks of the whole context
        assert eng.last_put["shared_kv_read_tokens"] == 2 * (at + 16)
        # the whole-context group's keys and pairs are that one query's,
        # not a causal chunk's; the window group's stay every position's
        assert eng.last_put["kv_g0_qk_pairs"] == at + 16 \
            == eng.last_put["qk_pairs"] == eng.last_put["kv_g0_read_tokens"]
        assert eng.last_put["kv_g1_qk_pairs"] == sum(
            min(at + i + 1, 16) for i in range(16))
    out = np.asarray(eng.put([3, 4], [[tokens[64]], tokens[:5]])[0])
    assert np.abs(out - want[64]).max() < 5e-6 * span
    assert eng.put_totals["xdec_rows"] == 4 + 2
    eng.flush(3)
    eng.flush(4)


def test_differential_attention_on_joined_pairs_is_its_definition():
    """The padded form the paged path runs — queries (q1 | 0) and
    (0 | q2) against K/V heads joined in pairs, then the pairs' rows
    combined — against two softmaxes over the heads themselves, with λ
    away from λ_init."""
    from deepspeed_tpu.models.mixers import attention
    from deepspeed_tpu.models.transformer import attention_reference

    T, nh, kvh, hd = 23, 8, 4, 8
    k = jax.random.split(jax.random.PRNGKey(5), 8)
    q = jax.random.normal(k[0], (1, T, nh, hd))
    kk = jax.random.normal(k[1], (1, T, kvh, hd))
    v = jax.random.normal(k[2], (1, T, kvh, hd))
    lp = {name: 0.4 * jax.random.normal(k[3 + i], (hd,))
          for i, name in enumerate(attention.DIFF_LAMBDAS)}
    lp["subln_w"] = 1.0 + 0.1 * jax.random.normal(k[7], (2 * hd,))
    lam = float(jnp.exp(jnp.sum(lp["lambda_q1"] * lp["lambda_k1"]))
                - jnp.exp(jnp.sum(lp["lambda_q2"] * lp["lambda_k2"])))
    assert abs(lam) > 0.05
    for window, depth in ((0, 3), (6, 9)):
        with jax.default_matmul_precision("highest"):
            rows = attention_reference(
                attention.diff_queries(q), attention.diff_keys(kk),
                attention.diff_keys(v), causal=True, window=window,
                scale=hd ** -0.5)
            got = attention.diff_combine(RUNS_CFG, rows, lp, depth)
            want = runs_block().diff_attention(
                q[0], kk[0], v[0], lp, depth, RUNS_ARCH, window, 8)
        np.testing.assert_allclose(np.asarray(got).reshape(T, -1),
                                   np.asarray(want), rtol=2e-5, atol=2e-6)


def test_the_memory_is_the_same_tokens_y_before_the_gate(runs_model):
    """What the gated memory units read: the output of layer 6's
    recurrence (the layer in front of the whole-context one), skip added,
    gate not applied — the reference's, position by position."""
    from deepspeed_tpu.models import hybrid
    from deepspeed_tpu.models.mixers import mamba1

    model, params, tokens, _ = runs_model
    b = runs_block()
    ids = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x, _ = b.hidden(params, ids, RUNS_ARCH, 16, upto=6)
        _, fed = b.hidden(params, ids, RUNS_ARCH, 16, upto=7)
        lp = jax.tree.map(lambda a: a[0], params["layers"]["run1_slot0"])
        h1 = hybrid.norm_of(RUNS_CFG)(x[None], lp, "attn_norm")
        T = len(tokens)
        out, _, _, memory = mamba1.mamba1_mixer(
            RUNS_CFG, h1, lp, jnp.zeros((1, 3, 64)), jnp.zeros((1, 4, 64)),
            jnp.full((1,), T))
    top = float(jnp.abs(fed["memory"]).max())
    assert float(jnp.abs(memory[0] - fed["memory"]).max()) < 2e-6 * top
    # and not the gated value the layer's own output projection reads
    z = (h1 @ lp["mamba1_w_in"])[0, :, 64:]
    assert float(jnp.abs(memory[0] * jax.nn.silu(z)
                         - fed["memory"]).max()) > 0.1 * top


@pytest.mark.parametrize("reader", range(3))
def test_a_lost_whole_context_block_reaches_every_reader(runs_model, reader):
    """The whole-context group's one layer is read by the layer that
    writes it and by every cross layer. With all but one reader's output
    projection silenced, a block of the sequence's table pointed at its
    neighbour's still moves the next logits: each of them reads the
    pool."""
    _, params, tokens, _ = runs_model
    readers = [("run1_slot1", 0), ("run2_slot1", 0), ("run2_slot1", 1)]
    layers = {k: dict(v) for k, v in params["layers"].items()}
    for i, (slot, period) in enumerate(readers):
        if i != reader:
            for name in ("wo", "wo_b"):
                layers[slot][name] = layers[slot][name].at[period].set(0.0)
    eng = runs_engine(runs_model, params=dict(params, layers=layers))
    got = []
    for uid, lose in ((1, False), (2, True)):
        feed(eng, uid, tokens[:48])
        if lose:
            seq = eng.state_manager.get_sequence(uid)
            seq.rows[0, 2] = seq.rows[0, 3]
        got.append(np.asarray(eng.put([uid], [[tokens[48]]])[0]))
        eng.flush(uid)
    assert np.abs(got[0] - got[1]).max() > 1e-4 * np.abs(got[0]).max()


def test_runs_refuse_what_assumes_one_resident_per_token_cache(runs_model,
                                                               devices8):
    from deepspeed_tpu.models.hybrid import ReleasedKVUnsupported

    model, params, tokens, _ = runs_model
    with pytest.raises(RecurrentStateUnsupported, match="prefix cache"):
        runs_engine(runs_model, enable_prefix_cache=True)
    with pytest.raises((RecurrentStateUnsupported, ReleasedKVUnsupported)):
        runs_engine(runs_model, kv_quant_enabled=True)
    eng = runs_engine(runs_model)
    feed(eng, 1, tokens[:40])
    with pytest.raises(RecurrentStateUnsupported, match="KV tier"):
        eng.configure_kv_tier(True)
    with pytest.raises(RecurrentStateUnsupported, match="trim_sequence"):
        eng.trim_sequence(1, 2)
    with pytest.raises(RecurrentStateUnsupported, match="verif"):
        eng.put([1], [[3, 4]], verify_width=2)
    with pytest.raises(ReleasedKVUnsupported):
        eng.export_sequence(1)
    eng.flush(1)
    from jax.sharding import Mesh

    mesh = Mesh(np.array(devices8[:2]), ("tensor",))
    with pytest.raises(RecurrentStateUnsupported, match="TP serving"):
        InferenceEngineV2(model, params=params, mesh=mesh,
                          config=RaggedInferenceEngineConfig(**SIZING))


@pytest.mark.parametrize("runs,what", [
    (((("gmu", "cross"), 2), (("mamba1", "window"), 4)), "no earlier run"),
    (((("mamba1", "window"), 4), (("gmu", "full"), 2)), "no earlier run"),
    (((("mamba1", "window"), 2), (("mamba1", "full"), 2),
      (("gmu", "cross"), 2)), "one period"),
    (((("mamba1", "window"), 2), (("mamba1", "full"), 1),
      (("gmu", "cross"), 2)), "num_layers"),
], ids=["nothing-in-front", "no-writer", "a-scanned-feeder", "depth"])
def test_a_layer_that_nothing_feeds_is_refused(runs, what):
    with pytest.raises(ValueError, match=what):
        dataclasses.replace(RUNS_CFG, layer_runs=runs, layer_pattern=None)
    # ... and outside layer_runs the kinds have nothing to read at all
    with pytest.raises(ValueError, match="layer_runs"):
        dataclasses.replace(RUNS_CFG, layer_runs=None, diff_attn=False,
                            sliding_window=None,
                            layer_pattern=("mamba1", "gmu"), num_layers=12)


# ---------------- a tiny Jamba: S6 layers with norms inside, 40 seats ------
# (AI21-Jamba2-3B's block: ``mamba1_inner_norm``, attention of four heads
# over ONE K/V head with no position term, a dense MLP behind every mixer;
# the published order of the kinds in little: S6 x 2, attention, S6 x 3,
# attention, S6 x 1)

JAMBA_CFG = TransformerConfig(
    vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=8,
    num_heads=4, num_kv_heads=1, head_size=8, max_seq_len=128,
    norm="rmsnorm", norm_eps=1e-6, activation="silu", position="rope",
    rope_kinds=(), tie_embeddings=True, dtype=jnp.float32,
    layer_runs=((("mamba1",), 2), (("full",), 1), (("mamba1",), 3),
                (("full",), 1), (("mamba1",), 1)),
    mamba1_inner_size=64, mamba1_state_size=4, mamba1_dt_rank=4,
    mamba1_conv_kernel=4, mamba1_inner_norm=True)
SEATS = 40


def test_a_tiny_jamba_with_forty_sequences_at_once_agrees_with_apply():
    """More rows a step than any engine ran before (the cap was 32
    everywhere): 40 sequences of different lengths prefilled in chunks of
    16, then stepped together through the pool and the slots, ``[40, 1]``
    a forward; ten finish, ten more take their seats — a reused slot
    starts from zero — and every sequence's logits are ``CausalLM.apply``'s
    on its own tokens."""
    model = CausalLM(JAMBA_CFG)
    params = model.init(jax.random.PRNGKey(4))
    flat, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(5), len(flat))
    params = jax.tree_util.tree_unflatten(tree, [
        leaf + 0.05 * jax.random.normal(k, leaf.shape)
        if "norm" in jax.tree_util.keystr(path) else leaf
        for (path, leaf), k in zip(flat, keys)])
    eng = InferenceEngineV2(model, params=params,
                            config=RaggedInferenceEngineConfig(
                                max_ragged_batch_size=SEATS * 16,
                                max_ragged_sequence_count=SEATS,
                                max_chunk_tokens=16, kv_blocks=SEATS * 16,
                                kv_block_size=8, max_tracked_sequences=64))
    sm = eng.state_manager
    assert sm.state_slots == SEATS
    assert sm.forward_cache["mamba1_ssm"].shape == (6, SEATS + 1, 4, 64)
    assert sm.forward_cache["k"].shape == (2, SEATS * 16, 1, 8, 8)
    steps = 3
    seqs = {uid: prompt(100 + uid, 5 + uid) for uid in range(SEATS)}
    apply = jax.jit(model.apply)

    def want(uid):
        """The whole forward's logits at the sequence's last position."""
        tokens = np.zeros((1, 64), np.int32)
        tokens[0, :len(seqs[uid])] = seqs[uid]
        with jax.default_matmul_precision("highest"):
            return np.asarray(apply(params, jnp.asarray(tokens))
                              )[0, len(seqs[uid]) - 1]

    def agree(got, uid):
        w = want(uid)
        assert np.abs(got - w).max() < 1e-5 * (w.max() - w.min()), uid

    def prefill(uids):
        """Every sequence's prompt, all rows of a put together, a chunk
        of 16 a row a put."""
        last = {}
        for at in range(0, max(len(seqs[u]) for u in uids), 16):
            rows = [u for u in uids if len(seqs[u]) > at]
            out = eng.put(rows, [seqs[u][at:at + 16] for u in rows])
            for i, u in enumerate(rows):
                if len(seqs[u]) <= at + 16:
                    last[u] = np.asarray(out[i])
        return last

    def step(uids, last):
        for u in uids:
            seqs[u] = seqs[u] + [int(np.argmax(last[u]))]
        out = eng.put(uids, [[seqs[u][-1]] for u in uids])
        assert eng.last_put["bucket_seqs"] == SEATS
        assert eng.last_put["ssm_rows_stepped"] == len(uids)
        return {u: np.asarray(out[i]) for i, u in enumerate(uids)}

    everyone = list(seqs)
    last = prefill(everyone)
    assert eng.occupancy()["state_slots_used"] == SEATS
    assert eng.can_schedule([SEATS], [1]) \
        == SchedulingResult.KVCacheLimitExceeded        # no seat left
    for _ in range(steps):
        last = step(everyone, last)
    for uid in everyone:
        agree(last[uid], uid)
    # ten finish; ten more take the seats they left
    for uid in everyone[:10]:
        eng.flush(uid)
    assert sm.free_state_slots == 10
    late = list(range(SEATS, SEATS + 10))
    seqs.update({uid: prompt(300 + uid, 70 - uid) for uid in late})
    last.update(prefill(late))
    running = everyone[10:] + late
    last = step(running, {u: last[u] for u in running})
    for uid in running[::7] + late:
        agree(last[uid], uid)
    for uid in running:
        eng.flush(uid)
    assert sm.allocator.free_blocks == sm.allocator.total_blocks
    assert sm.free_state_slots == sm.state_slots == SEATS


# -------------- the one-token step where the state lies (``s6_step``) ------

def _serve_three(model, params, kernel, monkeypatch):
    """Three sequences prefilled in chunks and stepped together, greedily,
    through a bucket of four rows (one is padding every step), in an
    engine of its own: the forward is traced under the hook as it stands.
    Returns (tokens by sequence, the last step's logits, the S6 leaf's
    rows a live sequence named, its scratch slot, the totals, the bucket
    rows of every trace of the kernel's function)."""
    from deepspeed_tpu.ops import selective_scan as s6

    monkeypatch.setattr(s6, "_FORCE_INTERPRET", kernel)
    traced = []
    inner = getattr(s6._step_in_kernel, "inner", s6._step_in_kernel)

    def counting(pool, layer, slots, *rest, **kw):
        traced.append(int(slots.shape[0]))
        return inner(pool, layer, slots, *rest, **kw)

    counting.inner = inner      # a later engine's wrapper goes round this
    monkeypatch.setattr(s6, "_step_in_kernel", counting)
    eng = InferenceEngineV2(model, params=params,
                            config=RaggedInferenceEngineConfig(
                                **dict(SIZING, compile_ahead=0)))
    seqs = {u: prompt(40 + u, n) for u, n in ((1, 21), (2, 5), (3, 37))}
    last = {u: feed(eng, u, list(t)) for u, t in seqs.items()}
    for _ in range(3):
        for u in seqs:
            seqs[u].append(int(np.argmax(last[u])))
        out = eng.put(list(seqs), [[t[-1]] for t in seqs.values()])
        assert eng.last_put["bucket_seqs"] == 4
        assert eng.last_put["ssm_rows_stepped"] == 3
        last = {u: np.asarray(out[i]) for i, u in enumerate(seqs)}
    sm = eng.state_manager
    leaf = np.asarray(sm.forward_cache["mamba1_ssm"])
    named = [sm.get_sequence(u).state_slot for u in seqs]
    return (seqs, last, leaf[:, named], leaf[:, sm.state_slots],
            dict(eng.put_totals), traced)


@pytest.mark.parametrize("which", ["runs", "jamba"])
def test_a_sequence_stepped_through_the_kernel_is_the_plain_forms(
        which, monkeypatch, request):
    """``s6_step`` interpreted against gather, ``s6_step``, scatter, in the
    engine: the same tokens, the logits and the live slots' state to
    float32 round-off; the scratch slot, which the plain form writes back
    as it was and the kernel never names, bit for bit the same; and
    ``ssm_rows_stepped`` is what went through the kernel -- every
    one-token forward's S6 layers trace it at the bucket's rows, no chunk
    forward's do, and the plain engine's none."""
    if which == "runs":
        model, params = request.getfixturevalue("runs_model")[:2]
    else:
        model = CausalLM(JAMBA_CFG)
        params = model.init(jax.random.PRNGKey(4))
    plain = _serve_three(model, params, False, monkeypatch)
    kernel = _serve_three(model, params, True, monkeypatch)
    assert kernel[0] == plain[0]                        # the tokens
    for u in plain[1]:
        span = plain[1][u].max() - plain[1][u].min()
        assert np.abs(kernel[1][u] - plain[1][u]).max() < 5e-6 * span
    np.testing.assert_allclose(kernel[2], plain[2], rtol=2e-5, atol=2e-6)
    np.testing.assert_array_equal(kernel[3], plain[3])
    assert kernel[4]["ssm_rows_stepped"] == 9 == plain[4]["ssm_rows_stepped"]
    assert kernel[4]["ssm_chunk_tokens"] == 21 + 5 + 37
    assert plain[5] == []
    # a trace a body of S6 layers of the one [4, 1] program
    assert kernel[5] and set(kernel[5]) == {4}
