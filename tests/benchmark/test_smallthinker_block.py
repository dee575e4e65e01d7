"""The ``smallthinker`` block as the benchmark finds it: the manifest with
its entries, the configuration against the catalog row it was drawn from,
the issue's arithmetic, the reference against the program's model at the
tiny twin's size — ``CausalLM.apply``, and prefill in chunks that cross
the twin's window then decode through both pools — each planted fault
failing the same comparison (the router fed the FFN's normed input, the
router fed the normed layer input, SiLU for ReLU, the gate dropped,
rotary on the whole-context layers, the softmax taken before the top-6
and left unnormalised), ``[0, n]`` held against none stated, the
ill-conditioned routing decisions left out, the scope names, the new
readers on hand-made contexts, and the cell rehearsed end to end on the
CPU under the real names."""

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

CELL, CONFIG = "smallthinker-21b-a3b.bulkgen", "smallthinker-21b-a3b"
NEW_READERS = ("sat_experts_share", "sat_moe_route_share",
               "sat_moe_rows_per_expert", "sat_gmm_roofline",
               "sat_logits_share", "sat_attn_window_share",
               "sat_attn_full_share", "sat_kv_window_blocks_peak_share",
               "sat_paged_attn_window_roofline")
CATALOG_FILE = "/opt/skills/guides/model-configs/architectures.jsonl"
CATALOG_NAME = "SmallThinker-21BA3B-Instruct"
TWINS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "twins")
PROMPT, STEPS = 100, 6


def block():
    return mf.find_module(mf.HERE, "blocks", "smallthinker")


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
    assert len(manifest["workloads"]) >= 11
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    assert info["block"].__name__.endswith("smallthinker")
    mix = info["traffic"]
    assert (mix["generator"], mix["loop"], mix["clients"]) \
        == ("stratified", "closed", 32)
    assert mix["prompt_tokens"] == {"median": 1536, "sigma": 1.0,
                                    "min": 64, "max": 8192}
    assert mix["output_tokens"] == {"median": 1536, "sigma": 0.6,
                                    "min": 256, "max": 3072}
    assert (mix["preroll_s"], mix["drain_s"], mix["schedule_seed"]) \
        == (30, 120, 0)
    assert info["cell"]["chips"] == 1 and info["workload"]["serving"] == {}
    assert info["workload"]["runner"] == "serve"
    assert "rate_rps" not in info["workload"]       # a closed loop
    mine = {m["name"] for m in mf.metrics_for(manifest, "per_layer", CELL)}
    assert set(NEW_READERS) <= mine
    # everything mistral-7b.batch reports but the dense model's roofline
    # (it would book the six window layers as whole-context ones). The two
    # readers of forwards wider than one token are listed: the schedule is
    # fixed, requests start at the same times in every run, and the traced
    # 5 s hold the first chunks of two of them, three forwards at the
    # least (a listed metric that finds nothing to read fails the traced
    # run)
    batch = {m["name"] for m in mf.metrics_for(manifest, "per_layer",
                                               "mistral-7b.batch")}
    assert batch - mine == {"sat_paged_attn_roofline"}
    assert info["workload"]["trace_s"] == 5
    assert mine - batch == set(NEW_READERS)
    assert {m["name"] for m in mf.metrics_for(manifest, "end_to_end", CELL)} \
        == {"serve_tok_s", "setup_s"}
    # appended: behind what was there
    at = lambda group, name: [x["name"] for x in manifest[group]  # noqa: E731
                              ].index(name)
    assert at("configs", CONFIG) > at("configs", "nemotron-3-super-120b-a12b")
    assert at("workloads", CELL) > at("workloads",
                                      "nemotron-3-super-120b-a12b.reason")
    assert at("per_layer", NEW_READERS[0]) > at("per_layer",
                                                "paged_primed_share")
    for m in manifest["per_layer"] + manifest["end_to_end"]:
        if CELL in m.get("workloads", ()) and m["name"] not in NEW_READERS:
            assert m["workloads"][-1] == CELL
            assert m["workloads"][0] == "mistral-7b.batch"


def test_the_schedule_is_the_issues():
    """Every block of 16 requests holds 36,567 prompt and 27,007 output
    tokens; one prompt of the sixteen lies just past the window (the one
    the check replays), three lie past it in all, and at most 11,264
    positions are ever run."""
    _, info = real()
    gen = mf.find_module(mf.HERE, "traffic", "stratified")
    stream = gen.requests(info["traffic"], 1000, 7)
    reqs = [next(stream) for _ in range(48)]
    check = info["config"]["check"]
    for at in range(0, 48, 16):
        prompts = sorted(len(r.prompt) for r in reqs[at:at + 16])
        outputs = sorted(r.new_tokens for r in reqs[at:at + 16])
        assert (sum(prompts), sum(outputs)) == (36567, 27007)
        assert prompts[0] == 238 and prompts[-3:] == [4217, 5738, 8192]
        assert (outputs[0], outputs[-1]) == (502, 3072)
        assert [p for p in prompts if check["min_prompt_tokens"] <= p
                <= check["max_prompt_tokens"]] == [4217]
    arch = info["config"]["transformer_config"]
    assert max(len(r.prompt) + r.new_tokens for r in reqs) \
        <= 8192 + 3072 == arch["max_seq_len"]
    # every checked prompt crosses the window, and its replay fits the
    # width the reference is run at (a multiple of 256)
    assert check["min_prompt_tokens"] > arch["sliding_window"]
    assert check["max_prompt_tokens"] + check["decode_steps"] <= 17 * 256


def test_the_configuration_is_the_catalog_rows_but_for_what_it_reduces():
    _, info = real()
    config, entry = info["config"], info["config_entry"]
    with open(CATALOG_FILE) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == CATALOG_NAME)
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == ["num_hidden_layers", "rope_layout",
                                "sliding_window_layout"]
    assert sorted(config["reduced"]) == sorted(entry["reduced"])
    for key, value in row["config"].items():
        if key not in entry["reduced"]:
            assert config[key] == value, key
        else:
            assert config["reduced"][key] == [value, config[key]], key
            assert config["published"][key] == value
    # the cut: the first two of thirteen whole periods, nothing else
    assert row["config"]["rope_layout"] == [0, 1, 1, 1] * 13 \
        == row["config"]["sliding_window_layout"]
    assert config["rope_layout"] == config["sliding_window_layout"] \
        == [0, 1, 1, 1] * 2
    b = info["block"]
    for c in (config, twin()):
        check_consistent(c, b)
        arch = c["transformer_config"]
        kinds = {0: "full", 1: "window"}
        assert [kinds[w] for w in c["sliding_window_layout"]] \
            == arch["layer_pattern"] * (arch["num_layers"] // 4)
        # rotated exactly where windowed
        assert c["rope_layout"] == c["sliding_window_layout"]
        assert arch["rope_kinds"] == ["window"]
        assert arch["num_layers"] == c["num_hidden_layers"] == 8
        assert arch["moe_held_experts"] == [0, c["moe_num_primary_experts"]]
        assert arch["num_heads"] // arch["num_kv_heads"] == 7
        assert (arch["moe_score_func"], arch["moe_activation"],
                arch["moe_router_input"]) == ("softmax", "reglu", "layer")
        assert c["moe_primary_router_apply_softmax"] and arch["moe_norm_topk"]
    arch = config["transformer_config"]
    assert (arch["vocab_size"], arch["moe_num_experts"], arch["moe_top_k"]) \
        == (151936, 64, 6)
    assert arch["max_seq_len"] <= config["max_position_embeddings"]
    for key in ("weights", "from_the_modelling_code", "positions_run",
                "left_out"):
        assert config["assumed"][key]
    for key in ("deployment", "published", "_reduced"):
        assert config[key]
    engine = config["engine"]
    assert (engine["kv_block_size"], engine["max_ragged_sequence_count"],
            engine["kv_blocks"]) == (64, 32, 6144)
    for key in ("_arithmetic", "_steps"):
        assert len(engine[key]) > 100
    for key in ("_tolerance", "_ties", "_prompt_tokens"):
        assert len(config["check"][key]) > 100
    # the twin keeps every switch of the published file's architecture
    tw = twin()["transformer_config"]
    assert set(tw) == set(arch)
    assert all(tw[k] == arch[k] for k in arch
               if isinstance(arch[k], (bool, str, list)) and k != "dtype"
               and k != "moe_held_experts")


@pytest.mark.parametrize("wrong", [{"head_dim": 64},
                                   {"moe_num_active_primary_experts": 8},
                                   {"moe_num_primary_experts": 32},
                                   {"moe_ffn_hidden_size": 1024},
                                   {"sliding_window_size": 2048},
                                   {"norm_topk_prob": False}])
def test_a_published_key_that_disagrees_with_the_program_is_refused(wrong):
    _, info = real()
    with pytest.raises(ValueError, match=next(iter(wrong))):
        check_consistent(dict(info["config"], **wrong), info["block"])


def test_the_arithmetic_is_the_issues():
    _, info = real()
    b, arch = info["block"], info["config"]["transformer_config"]
    M = 1e6
    # q, o: 2560 x 3584 each; k, v: 2560 x 512 each
    assert b.attention_matmul_params(arch) == 2 * 2560 * 3584 \
        + 2 * 2560 * 512 == 20_971_520
    assert b.expert_matmul_params(arch) == 3 * 2560 * 768 == 5_898_240
    assert b.layer_kinds(arch) == {"window": 6, "full": 2}
    assert b.attention_calls(arch) == [(0, 2), (4096, 6)]
    # a token's matmuls: attention + router + its six experts a layer, and
    # the head: 8 x 56.5 M + 389 M
    per_layer = 20_971_520 + 2560 * 64 + 6 * 5_898_240
    assert per_layer / M == pytest.approx(56.5, abs=0.05)
    assert b.matmul_params(arch) == 8 * per_layer + 2560 * 151936
    assert peaks.forward_flops(b, arch, 1, 0) == 2.0 * b.matmul_params(arch)
    # the published model from the same functions: 21.5 B whole, 3.3 B a
    # token
    layer = 20_971_520 + 2560 * 64 + 64 * 5_898_240
    assert layer / M == pytest.approx(398.63, abs=0.01)
    assert (52 * layer + 2 * 151936 * 2560) / 1e9 == pytest.approx(21.5,
                                                                   abs=0.05)
    assert (52 * per_layer + 151936 * 2560) / 1e9 == pytest.approx(3.3,
                                                                   abs=0.05)
    # what the program's model holds: 3,967 M parameters, 7.93 GB
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig

    cfg = TransformerConfig(**dict(arch, dtype=jnp.bfloat16))
    shapes = jax.eval_shape(CausalLM(cfg).init, jax.random.PRNGKey(0))
    total = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert total / M == pytest.approx(3967, abs=0.5)
    assert 2 * total / 1e9 == pytest.approx(7.93, abs=0.01)
    assert cfg.kv_groups() == ((0, 2), (4096, 6))
    assert cfg.num_sparse_layers == 8 == cfg.num_attn_layers
    # K/V 2 KiB a token a layer: a 64-token block is 256 KiB in the
    # whole-context group and 768 KiB in the window group
    layouts = cfg.kv_layouts(64)
    block_bytes = [n * sum(2 * int(np.prod(shape))
                           for shape in layout.values())
                   for (_, n), layout in zip(cfg.kv_groups(), layouts)]
    assert block_bytes == [256 * 1024, 768 * 1024]
    # a [32, 1] step: 192 pairs a layer over 64 experts, 61 of them hit
    # under even routing; a 2,048-token chunk hits them all
    assert b.experts_hit(arch, 32) == pytest.approx(
        64 * (1 - (58 / 64) ** 32)) == pytest.approx(61.3, abs=0.1)
    assert b.experts_hit(arch, 2048) == pytest.approx(64)
    step = b.gmm_cost(arch, 32)
    assert step["flops"] == 8 * 2.0 * 5_898_240 * 192
    assert step["bytes"] == pytest.approx(
        8 * 2 * (b.experts_hit(arch, 32) * 5_898_240
                 + 192 * (3 * 2560 + 3 * 768)))
    # the step's bytes, reckoned as the issue does: experts 5.8 GB of 6.9
    experts = step["bytes"]
    rest = 8 * 2 * 20_971_520 + 2 * 2560 * 151936
    assert experts / 1e9 == pytest.approx(5.8, abs=0.05)
    assert (experts + rest) / 1e9 == pytest.approx(6.9, abs=0.05)
    assert 1e3 * (experts + rest) / 819e9 == pytest.approx(8.4, abs=0.1)
    chunk = b.gmm_cost(arch, 2048)
    assert chunk["flops"] == 8 * 2.0 * 5_898_240 * 2048 * 6
    # ... and is bound by its bytes all the same: 64 experts' weights and
    # 12,288 pairs' rows in and out of three matmuls, 8.0 GB
    assert chunk["bytes"] / 1e9 == pytest.approx(8.0, abs=0.05)
    assert chunk["bytes"] / 819e9 > chunk["flops"] / 197e12
    assert peaks.roofline_seconds(chunk, "TPU v5 lite") \
        == pytest.approx(chunk["bytes"] / 819e9)
    # the paged kernel at the stated head size, 7 query heads a K/V head
    cost = b.paged_attention_cost(arch, 33, 5000, 70000)
    assert cost["flops"] == 4.0 * 28 * 128 * 70000
    assert cost["bytes"] == 2.0 * 4 * 128 * 2 * 5000 + 2.0 * 28 * 128 * 2 * 33


# ----------------------------------------- the reference and the program

LOUD = 4.0


@pytest.fixture(scope="module")
def tiny():
    """The twin's model, its weights with every projection ``LOUD`` times
    as large, a prompt and the reference's answer to it (every position
    answered), built once. At the seed's own scale (0.02 at a width of 32)
    the experts move the logits by 1e-4 of range or less, and a gate
    dropped would pass any limit a float32 comparison can hold; four
    times as loud each planted fault below shows by 1e-3 of range or more
    while program and reference still agree to 2e-6."""
    import jax.numpy as jnp

    from benchmark.model import seeded_params
    from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig

    arch = twin()["transformer_config"]
    model = CausalLM(TransformerConfig(**dict(arch, dtype=jnp.float32)))
    params = seeded_params(model, 3, jnp.float32)
    params = dict(params, layers={
        slot: {name: a if name.endswith("norm_w") else LOUD * a
               for name, a in lp.items()}
        for slot, lp in params["layers"].items()})
    tokens = np.random.default_rng(4).integers(
        0, arch["vocab_size"], size=PROMPT + STEPS).tolist()
    want, margins = block().tie_margins(
        params, np.asarray(tokens, np.int32), arch, 16)
    # (the K/V rows behind the positions' have a test of their own)
    return (arch, model, params, tokens, np.asarray(want)[:len(tokens)],
            np.asarray(margins)[:, :len(tokens)])


def test_reference_agrees_with_the_programs_model(tiny):
    import jax
    import jax.numpy as jnp

    arch, model, params, tokens, want, margins = tiny
    assert margins.shape == (8, PROMPT + STEPS) and (margins > 0).all()
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(model.apply)(
            params, jnp.asarray(tokens)[None]))[0]
    assert np.abs(got - want).max() < 2e-6 * (want.max() - want.min())
    ids = np.asarray([tokens + tokens[:1]], np.int32)
    logits, aux = jax.jit(lambda p, t: model.apply(p, t, return_aux=True))(
        params, ids[:, :-1])
    logp = jax.nn.log_softmax(logits[0])
    nll = -float(jnp.mean(jnp.take_along_axis(
        logp, jnp.asarray(ids[0, 1:])[:, None], -1)))
    assert float(block().loss(params, ids, arch, q_block=16)) \
        == pytest.approx(nll, rel=1e-5)
    assert np.isfinite(float(aux))


def _engine(model, params, **sizing):
    from deepspeed_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2, RaggedInferenceEngineConfig)

    return InferenceEngineV2(model, params=params,
                             config=RaggedInferenceEngineConfig(**dict(
                                 twin()["engine"], compile_ahead=0, **sizing)))


def _served(engine, tokens, uid=7, chunk=32):
    """Prefill in chunks, then decode the given tokens: the logits at the
    prompt's last position and at every later one."""
    got = []
    for at in range(0, PROMPT, chunk):
        out = engine.put([uid], [tokens[at:min(at + chunk, PROMPT)]])
    got.append(np.asarray(out[0]))
    for i in range(PROMPT, PROMPT + STEPS):
        got.append(np.asarray(engine.put([uid], [[tokens[i]]])[0]))
    return np.stack(got)


def _worst(got, want):
    return np.abs(got - want[PROMPT - 1:PROMPT + STEPS]).max() \
        / (want.max() - want.min())


@pytest.fixture(scope="module")
def run(tiny):
    """The prompt served once — four chunks of 32 tokens, the window of
    32 crossed in the second, then six decode steps — with what the
    engine counted."""
    arch, model, params, tokens, *_ = tiny
    engine = _engine(model, params)
    sm = engine.state_manager
    shapes = {k: v.shape for k, v in sm.forward_cache.items()}
    logits = _served(engine, tokens)
    last = dict(engine.last_put)
    engine.flush(7)
    return dict(
        logits=logits, totals=dict(engine.put_totals), shapes=shapes,
        last=last, free=[g.allocator.free_blocks for g in sm.groups],
        total=[g.allocator.total_blocks for g in sm.groups])


def test_chunks_then_decode_through_both_pools(tiny, run):
    """Logits, not sampled ids, at float32: the limit of 1e-4 of range is
    the twins' ``check`` tolerance, fifty times what two float32
    implementations of these equations differ by here (2e-7 of range) and
    a tenth of the least any fault below moves them."""
    arch, *_, want, _ = tiny
    # two whole-context layers' pool of kv_blocks, six window layers' by
    # the engine's rule: 4 x (32 / 8 + 2) + 96 / 8
    assert run["shapes"] == {"k": (2, 128, 2, 8, 8), "v": (2, 128, 2, 8, 8),
                             "k1": (6, 36, 2, 8, 8), "v1": (6, 36, 2, 8, 8)}
    assert _worst(run["logits"], want) < 2e-6 < 1e-4
    n = PROMPT + STEPS
    totals, last = run["totals"], run["last"]
    assert totals["tokens_valid"] == n
    # every layer routes top-6 of 16, all held: held = routed
    assert totals["moe_rows_routed"] == totals["moe_rows_held"] == n * 6 * 8
    assert last["moe_rows_routed"] == last["moe_rows_held"] == 6 * 8
    # the last step read the whole context in one group and the window in
    # the other, and the window group had handed blocks back
    assert (last["kv_g0_window"], last["kv_g1_window"]) == (0, 32)
    assert last["kv_g0_read_tokens"] == n and last["kv_g1_read_tokens"] == 32
    assert totals["kv_blocks_released"] > 0
    assert last["kv_g1_in_use"] < last["kv_g0_in_use"]
    assert last["kv_bytes_resident"] < last["kv_bytes_unreleased"]


def test_another_chunking_and_a_second_sequence_agree(tiny, run):
    arch, model, params, tokens, want, _ = tiny
    span = want.max() - want.min()
    engine = _engine(model, params)
    other = _served(engine, tokens, chunk=24)
    assert np.abs(other - run["logits"]).max() < 2e-6 * span
    again = _served(engine, tokens, uid=8)
    assert np.abs(again - run["logits"]).max() < 2e-6 * span


def test_every_block_of_both_groups_comes_back(run):
    assert run["free"] == run["total"] == [128, 36]


def test_the_pallas_walk_at_a_group_of_seven_and_its_counted_steps(
        tiny, monkeypatch):
    """The paged kernel itself (interpret mode) at 7 query heads a K/V
    head, through both layer groups: three chunks across the window's
    edge and two decode steps agree with the reference; and
    ``attn_steps`` / ``attn_steps_primed`` of a traced put count the
    kernel's live grid steps of both groups' calls — more than either
    group's walk alone."""
    from deepspeed_tpu.ops import paged_attention as pa
    from deepspeed_tpu.telemetry import Tracer

    arch, model, params, tokens, want, _ = tiny
    monkeypatch.setattr(pa, "_FORCE_INTERPRET", True)
    engine = _engine(model, params)
    engine.tracer = Tracer()
    assert [leaf for leaf, _ in engine._walks] == ["k", "k1"]
    assert [shape["window"] for _, shape in engine._walks] == [0, 32]
    got = []
    for at in range(0, 96, 32):
        got.append(np.asarray(engine.put([3], [tokens[at:at + 32]])[0]))
    last = dict(engine.last_put)
    for i in (96, 97):
        got.append(np.asarray(engine.put([3], [[tokens[i]]])[0]))
    rows = [31, 63, 95, 96, 97]
    assert np.abs(np.stack(got) - want[rows]).max() \
        < 2e-6 * (want.max() - want.min())
    one = [pa.grid_steps(np.asarray([64]), np.asarray([32]), chunk=32,
                         q_dtype=model.cfg.dtype,
                         pool_dtype=engine.state_manager.forward_cache[
                             leaf].dtype, table_blocks=32, **shape)
           for leaf, shape in engine._walks]
    assert last["attn_steps"] == one[0][0] + one[1][0] > max(
        one[0][0], one[1][0])
    assert last["attn_steps_primed"] == one[0][1] + one[1][1]
    engine.flush(3)
    pa._grid_shape.cache_clear()


def _changed(tiny, **change):
    """The program's logits under an architecture that differs by
    ``change``, on the same weights."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig

    arch, _, params, tokens, *_ = tiny
    other = CausalLM(TransformerConfig(**dict(arch, dtype=jnp.float32,
                                              **change)))
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(other.apply)(
            params, jnp.asarray(tokens)[None]))[0, PROMPT - 1:]


@pytest.mark.parametrize("change", [
    {"moe_router_input": "ffn"}, {"moe_activation": "silu"},
    {"rope_kinds": None}, {"moe_norm_topk": False}, {"norm_eps": 1e-2}],
    ids=["router-fed-the-ffns-normed-input", "silu-for-relu",
         "rotary-on-the-whole-context-layers",
         "softmax-before-the-top-6-unnormalised", "another-epsilon"])
def test_a_switch_thrown_the_other_way_fails(tiny, change):
    """The same weights under an architecture that differs in one switch,
    against the reference: the comparison that passes at 2e-6 fails by
    orders."""
    want = tiny[4]
    assert _worst(_changed(tiny), want) < 2e-6
    assert _worst(_changed(tiny, **change), want) > 1e-3


def test_the_router_fed_the_normed_layer_input_fails(tiny, monkeypatch):
    """What a reader of 'router before attention' might build instead:
    the router behind ``input_layernorm``, on what attention reads."""
    from deepspeed_tpu.models import hybrid

    arch, model, *_ = tiny
    want = tiny[4]
    plain = hybrid._router_logits
    monkeypatch.setattr(hybrid, "_router_logits", lambda rows, lp: plain(
        hybrid.block_norm(model.cfg, rows, lp["attn_norm_w"]), lp))
    assert _worst(_changed(tiny), want) > 1e-3


def test_the_gate_dropped_fails(tiny, monkeypatch):
    """``down(relu(up(x)))``, what ``_ragged_expert_ffn`` made of a
    ``"relu"`` handed a gate before it refused one."""
    from deepspeed_tpu.moe import grouped

    want = tiny[4]
    plain = grouped._ragged_expert_ffn
    monkeypatch.setattr(
        grouped, "_ragged_expert_ffn",
        lambda st, gs, w_in, w_out, w_gate, activation, dtype, **kw: plain(
            st, gs, w_in, w_out, None, "relu", dtype, **kw))
    assert _worst(_changed(tiny), want) > 1e-3


def test_all_held_stated_is_none_stated(tiny):
    """``moe_held_experts [0, n]`` is stated so that the counter's reader
    has a number to divide by; it is the model with none stated, bit for
    bit, in the program and in the reference."""
    arch, _, params, tokens, want, _ = tiny
    assert arch["moe_held_experts"] == [0, arch["moe_num_experts"]]
    assert np.array_equal(_changed(tiny),
                          _changed(tiny, moe_held_experts=None))
    unstated = dict(arch, moe_held_experts=None)
    again = np.asarray(block().tie_margins(
        params, np.asarray(tokens, np.int32), unstated, 16)[0])
    assert np.array_equal(again[:len(tokens)], want)


def test_a_share_of_the_experts_adds_up_to_the_layer(tiny):
    """The general form the reference keeps: four shares of a quarter of
    the experts, each routed over all sixteen, summed, are the layer — in
    the reference, and in the program's ``moe_ffn`` given each share's
    weights and the same early logits."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models import hybrid
    from deepspeed_tpu.models.transformer import TransformerConfig

    arch, _, params, *_ = tiny
    b = block()
    lp = jax.tree.map(lambda a: a[0], params["layers"]["slot1"])
    u = jax.random.normal(jax.random.PRNGKey(4), (50, arch["hidden_size"]))
    x = jax.random.normal(jax.random.PRNGKey(5), (50, arch["hidden_size"]))
    E, n = arch["moe_num_experts"], arch["moe_num_experts"] // 4
    with jax.default_matmul_precision("highest"):
        r = x @ lp["router_wg"]
        whole = b.routed_part(u, r, lp, arch, held=(0, E))
        parts, program = [], []
        for lo in range(0, E, n):
            share = dict(lp, **{k: lp[k][lo:lo + n]
                                for k in ("w_in", "w_gate", "w_out")})
            parts.append(b.routed_part(u, r, share, arch, held=(lo, n)))
            cfg = TransformerConfig(**dict(arch, dtype=jnp.float32,
                                           moe_held_experts=(lo, n)))
            program.append(hybrid.moe_ffn(cfg, u[None], share,
                                          router_logits=r)[0][0])
    assert np.allclose(sum(parts), whole, atol=1e-5)
    assert np.allclose(sum(program), whole, atol=1e-4)
    assert all(float(jnp.abs(p).max()) > 0 for p in parts)


# ------------------------------------------- ill-conditioned routing decisions

def test_the_margin_of_a_routing_decision_against_hand_counts():
    """Logits 6, 5, 4 | 3.5, 2, 1, 0, 0 at top-3: the decision's margin is
    the gap 4 - 3.5 in spreads of the eight logits; the weights are the
    softmax over the three chosen, or, unnormalised, their probabilities
    under the softmax over all eight."""
    import jax.numpy as jnp

    b = block()
    r = jnp.asarray([[2.0, 6.0, 0.0, 3.5, 5.0, 1.0, 4.0, 0.0]] * 2)
    arch = {"moe_top_k": 3, "moe_norm_topk": True}
    weights, experts, margin = b._route(r, arch, (0, 8))
    assert experts.tolist() == [[1, 4, 6]] * 2
    e = np.exp([6.0, 5.0, 4.0])
    assert np.allclose(weights, e / e.sum())
    assert np.allclose(margin, 0.5 / np.std(np.asarray(r[0])))
    loose, _, _ = b._route(r, dict(arch, moe_norm_topk=False), (0, 8))
    assert np.allclose(loose, e / np.exp(np.asarray(r[0])).sum())
    # a share that holds neither expert at the edge: the nearest of its own
    _, _, far = b._route(r, arch, (1, 1))            # expert 1: logit 6
    assert np.allclose(far, (6.0 - 3.5) / np.std(np.asarray(r[0])))


def test_logits_answer_exactly_where_every_decision_is_well_conditioned(
        tiny, monkeypatch):
    arch, _, params, tokens, whole, margins = tiny
    b = block()
    least = margins.min(axis=0)
    # the middle margin as the limit: half the positions get no answer
    monkeypatch.setattr(b, "TIE_MARGIN", float(np.median(least)))
    got = np.asarray(b.logits(params, np.asarray(tokens, np.int32), arch,
                              q_block=16))
    unanswered = np.isnan(got).all(axis=-1)
    T = len(tokens)
    assert (unanswered[:T] == (least < b.TIE_MARGIN)).all()
    assert 40 < unanswered[:T].sum() < 66
    assert (got[:T][~unanswered[:T]] == whole[~unanswered[:T]]).all()
    # behind the positions' rows, every eighth position's K/V of the first
    # window layer (layer 1), last row first: unanswered where the one
    # decision in front of that layer (layer 0's) is ill-conditioned
    assert got.shape[0] == T + -(-T // b.KV_STRIDE)
    ahead = margins[0, ::b.KV_STRIDE][::-1]
    assert (unanswered[T:] == (ahead < b.TIE_MARGIN)).all()
    assert 0 < unanswered[T:].sum() < len(ahead)
    width = 2 * arch["num_kv_heads"] * arch["head_size"]
    assert (got[T:][~unanswered[T:], width:] == 0).all()
    assert np.abs(got[T:][~unanswered[T:], :width]).min() > 0


def test_an_unanswered_position_is_not_compared_and_an_answered_one_is(
        tiny, monkeypatch):
    """Through the harness's own ``check_logits``: a reference row that is
    NaN throughout is masked out and counted, and an engine that is wrong
    at every position (its router fed the FFN's input) is caught as long
    as one compared position is answered."""
    from benchmark import serve_runner as sr
    from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig

    arch, model, params, tokens, _, margins = tiny
    b = block()
    import jax.numpy as jnp

    wrong = CausalLM(TransformerConfig(**dict(
        arch, dtype=jnp.float32, moe_router_input="ffn")))
    spoiled = _engine(wrong, params)
    info = {"config": dict(twin(), transformer_config=arch), "block": b}
    prompt = tokens[:70]

    def check(limit):
        monkeypatch.setattr(b, "TIE_MARGIN", limit)
        return sr.check_logits(spoiled, params, info, [prompt], 2, 1e-4, 1e-4)

    record = check(0.0)                 # every position answered: caught
    assert not record["ok"] and record["max_rel_err"] > 1e-3
    # a chunk's last row, 32 positions one token at a time, two more, and
    # the K/V of the window's four live positions in eight
    assert (record["compared"], record["unanswered"]) == (39, 0)
    record = check(1e9)                 # none answered: nothing compared
    assert record["ok"]
    assert (record["compared"], record["unanswered"]) == (0, 39)
    # and the sound engine passes where positions are answered
    sound = _engine(model, params)
    monkeypatch.setattr(b, "TIE_MARGIN", 0.0)
    record = sr.check_logits(sound, params, info, [prompt], 2, 1e-4, 1e-4)
    assert record["ok"] and 0 < record["max_rel_err"] < 1e-5


def test_the_blocks_replay_steps_the_prompts_tail_as_sequences_side_by_side(
        tiny, monkeypatch):
    """``replay`` against the harness's ``causal_replay``: the same
    request, the same greedy tokens, the same logits where both read —
    and the prompt's tail read besides, by as many sequences as the
    engine takes (four here), each cut shorter than the last, stepping
    together through the prompt's own tokens; every row agrees with the
    reference's forward, and every block is back."""
    from benchmark import serve_runner as sr

    arch, model, params, tokens, want, _ = tiny
    b = block()
    assert b.TAIL_ROWS == 32
    monkeypatch.setattr(b, "TAIL_ROWS", 8)
    engine = _engine(model, params)
    prompt = tokens[:90]
    (mine, rows, got), = b.replay(engine, 5, prompt, 3)
    engine.flush(5)
    (theirs, few, ref), = sr.causal_replay(engine, 6, prompt, 3)
    engine.flush(6)
    assert mine == theirs and len(mine) == 93
    # each sequence's chunked part ends a row, then eight steps of four
    # rows, then the first sequence alone
    heads = [82, 74, 66, 58]
    assert list(rows[:-5]) == [h - 1 for h in heads] \
        + [h + i for i in range(8) for h in heads] + [90, 91, 92]
    assert sorted(set(rows[:-5])) == list(range(57, 93)) and len(got) == 44
    # and the window group's K/V as the first sequence holds it at the
    # end: 93 positions seen, a window of 32, blocks of 8 — positions 56,
    # 64 ... 88 of every eighth, as rows counted from the end
    assert list(rows[-5:]) == [-1 - at // 8 for at in (56, 64, 72, 80, 88)]
    assert list(few) == list(range(89, 93))
    span = want.max() - want.min()
    mine_last = [got[list(rows).index(89, 4)]] + got[-8:-5]
    assert np.abs(np.stack(mine_last) - np.stack(ref)).max() < 2e-6 * span
    # the prompt's own positions agree with the reference's forward
    again = np.asarray(b.tie_margins(
        params, np.asarray(mine + [0] * 3, np.int32), arch, 16)[0])
    assert np.abs(np.stack(got) - again[list(rows)]).max() < 2e-6 * span
    # a table entry that points at its neighbour's block reads its K/V
    from benchmark.controls import LostBlock

    (_, rows, lost), = b.replay(LostBlock(engine, 1), 8, prompt, 3)
    engine.flush(8)
    off = np.abs(np.stack(lost[-5:]) - again[list(rows[-5:])]).max(axis=1)
    assert (off > 1e-3 * span).sum() == 1 and (off < 2e-6 * span).sum() == 4
    # a prompt too short for a second sequence: one, over half of it
    (_, rows, got), = b.replay(engine, 7, tokens[:11], 0)
    engine.flush(7)
    assert list(rows) == list(range(5, 11)) + [-1, -2]
    sm = engine.state_manager
    assert all(g.allocator.free_blocks == g.allocator.total_blocks
               for g in sm.groups)


# ------------------------------------------------------ scopes and readers

def test_the_new_scope_names_resolve_through_the_blocks_scopes():
    b = block()
    body = "jit(_forward)/layers/while/body/closed_call/"
    cases = {
        # the router ahead of attn_norm, a scope of its own below layers
        body + "router/dot_general:": "router",
        body + "attn_norm/mul:": "attn_norm",
        body + "window_attn/attend/paged_attention/pallas_call:": "attend",
        body + "full_attn/attend/paged_attention/pallas_call:": "attend",
        body + "window_attn/qkv/dot_general:": "qkv",
        body + "full_attn/kv_write/scatter:": "kv_write",
        body + "mlp/experts/jit(gmm)/pallas_call:": "experts",
        body + "mlp/experts/sort:": "experts",
        body + "mlp/add:": "mlp",
        body + "dynamic_slice:": scopes.SCAN_OVERHEAD,
        "jit(_forward)/logits/dot_general:": "logits",
        "jit(_forward)/logits/argmax:": "logits",
    }
    for op_name, want in cases.items():
        assert scopes.scope_of(op_name, b.SCOPES) == want, op_name
    assert set(b.ATTN_SCOPES.values()) < set(b.SCOPES)


def test_the_programs_forward_carries_the_scopes(tiny):
    """The names above are the program's: the twin's chunk forward, as
    compiled, holds every one of them, and its router's matmul stands
    ahead of the mixer, outside ``mlp``."""
    import re

    import jax.numpy as jnp

    arch, model, params, *_ = tiny
    engine = _engine(model, params)
    sm = engine.state_manager
    text = engine.paged.forward.lower(
        engine.params, sm.forward_cache, jnp.zeros((1, 32), jnp.int32),
        jnp.zeros((1,), jnp.int32), jnp.full((1,), 32, jnp.int32),
        jnp.zeros((2, 1, 32), jnp.int32)).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for name in block().SCOPES:
        assert any(f"/{name}/" in n or n.endswith("/" + name)
                   for n in names), name
    assert any("/router/" in n for n in names)
    assert not any("/mlp/router" in n for n in names)


def _ev(line, name, start, dur, plane="/device:TPU:0", **extra):
    return dict(plane=plane, line=line, name=name, start=start, dur=dur,
                **extra)


def hand_made_context(monkeypatch):
    """A 10 s window, two executions of the forward. A chunk forward 1..5
    ([1x128]): the router 1..1.25, a window layer's paged kernel 1.25..2.5,
    a full layer's 2.5..3, the experts 3..4.5 (their gmm kernel 3.5..4.5),
    the head 4.5..5. A decode step 6..8 ([2x1]): a window layer's qkv
    6..6.5, experts 6.5..7.5 (gmm the whole of it), the head 7.5..8."""
    _, info = real()
    body = "jit(_forward)/layers/while/body/closed_call/"
    call = " custom-call(bf16[8]{0} %q), custom_call_target=\"tpu_custom_call\""
    op = lambda n, a, d, scope: _ev(                            # noqa: E731
        trace.OPS_LINE, f"%fusion.{n} = bf16[8]{{0}} fusion(%a)", a, d,
        op_name=scope + "/dot_general:")
    kernel = lambda name, a, d, scope: _ev(                     # noqa: E731
        trace.OPS_LINE, f"%{name}.1 = bf16[8]{{0}}" + call, a, d,
        op_name=body + scope + "/pallas_call:")
    events = [
        _ev("python3", trace.WINDOW, 0.0, 10.0, plane="/host:CPU"),
        _ev("python3", "bench:forward[1x128]", 0.9, 0.2, plane="/host:CPU"),
        _ev("python3", "bench:forward[2x1]", 5.9, 0.2, plane="/host:CPU"),
        _ev(trace.MODULES_LINE, "jit__forward(1)", 1.0, 4.0),
        _ev(trace.MODULES_LINE, "jit__forward(2)", 6.0, 2.0),
        op(1, 1.0, 0.25, body + "router"),
        kernel("paged_attention", 1.25, 1.25,
               "window_attn/attend/paged_attention"),
        kernel("paged_attention", 2.5, 0.5, "full_attn/attend/paged_attention"),
        op(2, 3.0, 0.5, body + "mlp/experts"),
        kernel("gmm", 3.5, 1.0, "mlp/experts/jit(gmm)"),
        op(3, 4.5, 0.5, "jit(_forward)/logits"),
        op(4, 6.0, 0.5, body + "window_attn/qkv"),
        kernel("gmm", 6.5, 1.0, "mlp/experts/jit(gmm)"),
        op(5, 7.5, 0.5, "jit(_forward)/logits"),
    ]
    probe = Probe()
    probe.spans += [
        ("forward", 1.0, 1.1, {"valid_tokens": 100, "kv_read_tokens": 9000,
                               "qk_pairs": 500000}),
        ("forward", 6.0, 6.1, {"valid_tokens": 2, "kv_read_tokens": 9100,
                               "qk_pairs": 9100})]
    record = lambda **over: dict({                              # noqa: E731
        "n_seqs": 2, "valid_tokens": 100, "kv_g0_window": 0,
        "kv_g0_in_use": 50, "kv_g0_total": 200, "kv_g0_read_tokens": 9000,
        "kv_g0_qk_pairs": 500000, "kv_g1_window": 4096, "kv_g1_in_use": 30,
        "kv_g1_total": 40, "kv_g1_read_tokens": 5000,
        "kv_g1_qk_pairs": 300000, "moe_rows_held": 100 * 6 * 8}, **over)
    spans = [
        {"name": "forward", "t_start": 1.0, "t_end": 1.1, "attrs": record()},
        {"name": "forward", "t_start": 6.0, "t_end": 6.1, "attrs": record(
            valid_tokens=2, kv_g0_in_use=60, kv_g1_in_use=20,
            kv_g0_read_tokens=9100, kv_g0_qk_pairs=9100,
            kv_g1_read_tokens=6000, kv_g1_qk_pairs=6000,
            moe_rows_held=2 * 6 * 8)}]
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


def _read_metric(ctx, name):
    return mf.find_module(mf.HERE, "layer_metrics", name).reduce(ctx)


def test_each_new_reader_reads_a_hand_made_trace(monkeypatch):
    ctx, info = hand_made_context(monkeypatch)
    arch, b = info["config"]["transformer_config"], info["block"]
    busy = 6.0
    read = lambda name: _read_metric(ctx, name)                 # noqa: E731
    assert read("sat_moe_route_share") == pytest.approx(100 * 0.25 / busy)
    assert read("sat_experts_share") == pytest.approx(100 * 2.5 / busy)
    assert read("sat_logits_share") == pytest.approx(100 * 1.0 / busy)
    assert read("sat_attn_window_share") == pytest.approx(100 * 1.75 / busy)
    assert read("sat_attn_full_share") == pytest.approx(100 * 0.5 / busy)
    assert read("sat_kv_window_blocks_peak_share") == pytest.approx(75.0)
    # (100 + 2) / 2 tokens a forward x 6 x 8 layers, over 8 layers x 64
    assert read("sat_moe_rows_per_expert") == pytest.approx(51 * 6 / 64)
    least = sum(peaks.roofline_seconds(b.gmm_cost(arch, t), "TPU v5 lite")
                for t in (100, 2))
    assert read("sat_gmm_roofline") == pytest.approx(100 * least / 2.0)
    least = sum(
        layers * peaks.roofline_seconds(
            b.paged_attention_cost(arch, tokens, r, p), "TPU v5 lite")
        for tokens, groups in ((100, ((9000, 500000), (5000, 300000))),
                               (2, ((9100, 9100), (6000, 6000))))
        for (r, p), (_, layers) in zip(groups, b.attention_calls(arch)))
    assert read("sat_paged_attn_window_roofline") == pytest.approx(
        100 * least / 1.75)
    for name in NEW_READERS:
        assert 0 < read(name) < 1e6, name
    # the readers are the ones the other cells' names call
    assert read("sat_gmm_roofline") == hybrid_readers.gmm_roofline(ctx)
    assert read("sat_paged_attn_window_roofline") \
        == kv_group_readers.paged_attention_roofline(ctx)


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
        assert _read_metric(ctx, name) is None, name
    # a trace of a program without the names or the counters, and a block
    # without the scopes or the cost functions: nothing, and no error
    ctx, info = hand_made_context(monkeypatch)
    bare = [dict(e, op_name="") if "op_name" in e else e
            for e in scopes.load("")]
    monkeypatch.setattr(scopes, "load", lambda path: bare)
    ctx._scopes = scopes.summarize(bare, chips=1)
    ctx.result["program_spans"] = [
        {"name": "forward", "t_start": 1.0, "t_end": 1.1,
         "attrs": {"n_seqs": 2, "valid_tokens": 100}}]
    ctx.info = dict(info, block=mf.find_module(mf.HERE, "blocks", "dense"))
    for name in NEW_READERS:
        assert _read_metric(ctx, name) is None, name


# ------------------------------------------------------------ the controls

def test_the_planted_faults_come_out_through_the_cells_own_check(
        checkout, capsys):  # noqa: F811
    """``python3 -m benchmark.controls`` at the twin's size: the engine
    as built passes the cell's check, and a lost K/V block of either
    layer group and weights through fp8 each fail it — through
    ``check_logits`` and the block's replay, one engine resident at a
    time."""
    from benchmark import controls

    rows = os.path.join(checkout, "rows.jsonl")
    rc = controls.main(["--config", CONFIG, "--seed", "3", "--prompt-tokens",
                        "120", "--rows-out", rows], root=checkout)
    lines = [json.loads(line) for line in
             capsys.readouterr().out.strip().splitlines()]
    assert [(x["control"], x["ok"]) for x in lines] == [
        ("served", True), ("lost_block_g0", False), ("lost_block_g1", False),
        ("fp8_weights", False)]
    assert rc == 0 and all(x["as_expected"] for x in lines)
    # a chunk's last row, 32 stepped, 3 greedy and the K/V of the
    # window's five live positions in eight, those compared that the
    # reference answers
    assert {x["compared"] + x["unanswered"] for x in lines} == {41}
    assert min(x["compared"] for x in lines) > 4
    served, *faults = lines
    assert served["max_rel_err"] < 1e-5 < 1e-4 < min(
        x["max_rel_err"] for x in faults)
    kept = [json.loads(line) for line in open(rows)]
    assert len(kept) == 4 * 41 and len(kept[0]["margins"]) == 8


# ----------------------------------------------------------- the rehearsal

@pytest.mark.parametrize("traced", [0, 1])
def test_the_cell_rehearsed_on_the_cpu(checkout, capsys, traced):  # noqa: F811
    """The whole runner over the engine at the tiny twin's size, under
    the real names (the twin and its mix reach the checkout through
    ``tests/conftest.py``, found by name): a closed loop of four callers,
    prompts in chunks across the window's edge beside decoding rows,
    blocks handed back while they live, every checked prompt past the
    window, the logits check against this block's reference, every block
    of both groups back."""
    manifest = mf.load(checkout)
    mf.validate(manifest, checkout)
    info = mf.resolve(manifest, CELL, checkout)
    assert info["config"] == twin()
    assert info["traffic"] == _read(os.path.join(TWINS, "traffic",
                                                 "bulkgen.json"))
    assert info["workload"] == _read(os.path.join(
        mf.HERE, "workloads", CELL + ".json"))
    line, extra = rehearse(checkout, capsys, CELL, traced)
    assert line["correct"], extra["why_not"]
    assert line["failed"] == 0 and line["attempted"] > 5
    check_line(line, manifest, CELL, "per_layer" if traced else "end_to_end")
    check = extra["counters"]["logits_check"]
    # half of each prompt's positions and four more; the reference
    # answers where every one of a row's eight routing decisions is
    # well-conditioned, a few rows of them
    assert check["sampled"] == 2 and check["compared"] > 0
    assert check["compared"] + check["unanswered"] >= 2 * (33 // 2 + 4)
    assert 0 < check["max_rel_err"] < 1e-4 and check["rms_rel_err"] < 1e-4
    if not traced:
        assert set(line["metrics"]) == {"serve_tok_s", "setup_s"}
        return
    # off the chip the counters and the spans are read, the device is not
    assert {"sat_batch_seqs_mean", "sat_pad_ratio", "sat_kv_blocks_peak_share",
            "sat_moe_rows_per_expert", "sat_kv_window_blocks_peak_share"} \
        <= set(line["metrics"]), sorted(line["metrics"])
    assert not {"sat_experts_share", "sat_gmm_roofline", "sat_logits_share",
                "sat_fwd_decode_dev_ms"} & set(line["metrics"])
    # a closed loop of four callers keeps the engine's four slots taken
    assert line["metrics"]["sat_batch_seqs_mean"]["value"] > 2
