"""The ``nemotron_h`` block as the benchmark finds it: the manifest with
its entries, the configuration against the catalog row it was drawn from,
the issue's arithmetic, the reference against the program's model at the
tiny twin's size — ``CausalLM.apply``, and prefill in chunks then decode
through the pool and the state slots — the held experts' share tied to
the uncut layer (four shares of a quarter of the experts, each through
``W_l2``, and the shared expert once), a rotary switched on, a gated
expert, a dropped routed scale and a norm behind its gate failing, every
block and slot coming back, the typed refusals, the scope names, the new
readers on hand-made contexts, and the cell rehearsed end to end on the
CPU under the real names. The twin's pattern is the published cut's:
eleven positions, an attention layer alone, five FFNs alone, five Mamba-2
layers alone; a 32-token chunk is two tiles of its chunk of 16."""

import json
import os

import numpy as np
import pytest
from test_benchmark_runners import (_read, _write, check_line,  # noqa: F401
                                    checkout, rehearse)

from benchmark import manifest as mf
from benchmark import scopes, ssm_readers
from benchmark.model import check_consistent

CELL, CONFIG = "nemotron-3-super-120b-a12b.reason", \
    "nemotron-3-super-120b-a12b"
NEW_READERS = ("mamba_share", "mamba_scan_share", "mamba_state_io_share",
               "moe_latent_share", "ssm_state_gbps")
SHARED_READERS = ("batch_seqs_mean", "host_step_share", "pad_ratio",
                  "fwd_decode_dev_ms", "dev_decode_ms_per_forward",
                  "dev_scan_overhead_share", "dev_kv_write_share",
                  "dev_unscoped_share", "step_pack_ms", "step_stage_ms",
                  "step_commit_ms", "idle_unspanned_share",
                  "idle_starved_share", "idle_launch_share",
                  "idle_no_work_share", "decode_time_chunk_share",
                  "decode_time_idle_share", "steps_overlapped_share",
                  "steps_starved_share", "experts_share", "moe_route_share")
CATALOG_FILE = "/opt/skills/guides/model-configs/architectures.jsonl"
CATALOG_NAME = "NVIDIA-Nemotron-3-Super-120B-A12B-BF16"
TWINS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "twins")
PROMPT, STEPS = 100, 6


def block():
    return mf.find_module(mf.HERE, "blocks", "nemotron_h")


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
    assert len(manifest["workloads"]) >= 10
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) \
        <= max(1, len(manifest["workloads"]) // 4)
    assert info["block"].__name__.endswith("nemotron_h")
    assert info["traffic"]["loop"] == "open"
    assert info["traffic"]["generator"] == "stratified"
    assert info["traffic"]["prompt_tokens"] == {
        "median": 512, "sigma": 0.8, "min": 128, "max": 4096}
    assert info["traffic"]["output_tokens"] == {
        "median": 768, "sigma": 0.6, "min": 256, "max": 2048}
    assert (info["traffic"]["preroll_s"], info["traffic"]["drain_s"],
            info["traffic"]["schedule_seed"]) == (25, 75, 0)
    assert info["cell"]["chips"] == 1 and info["workload"]["serving"] == {}
    assert info["workload"]["rate_rps"] > 0
    mine = {m["name"] for m in mf.metrics_for(manifest, "per_layer", CELL)}
    assert set(NEW_READERS) | set(SHARED_READERS) <= mine
    # left off (PERF.md section 4 says why of each): an expectation that
    # read over 100% elsewhere, rooflines of a kernel one layer in eleven
    # calls, a count that divides by every layer where five hold experts,
    # and what moves a metric the cell does not report
    assert not mine & {"gmm_roofline", "paged_attn_roofline",
                       "paged_attn_hybrid_roofline", "moe_rows_per_expert",
                       "state_slots_peak_share", "gdn_share",
                       "lightning_share", "latent_attn_share", "mfu"}
    # decode-led: judged on the time between tokens
    ends = {m["name"] for m in mf.metrics_for(manifest, "end_to_end", CELL)}
    assert {"tpot_p90_ms", "setup_s"} <= ends <= {"tpot_p90_ms", "setup_s",
                                                  "ttft_p90_ms"}
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert all(by_name[name]["moves"] in ends for name in mine)
    for name in NEW_READERS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "tpot_p90_ms"
        assert by_name[name]["layer"] == "paged forward"
    at = lambda group, name: [e["name"] for e in manifest[group]  # noqa: E731
                              ].index(name)
    assert at("configs", CONFIG) > at("configs", "minicpm-sala")
    assert at("workloads", CELL) > at("workloads", "minicpm-sala.deepctx")
    assert at("per_layer", "mamba_share") > at("per_layer",
                                               "paged_attn_mask_roofline")


def test_the_configuration_is_the_catalog_rows_but_for_what_it_reduces():
    _, info = real()
    config, entry = info["config"], info["config_entry"]
    with open(CATALOG_FILE) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == CATALOG_NAME)
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == ["num_hidden_layers",
                                "hybrid_override_pattern",
                                "n_routed_experts", "vocab_size",
                                "num_nextn_predict_layers"]
    assert sorted(config["reduced"]) == sorted(entry["reduced"])
    for key, value in row["config"].items():
        if key not in entry["reduced"]:
            assert config[key] == value, key
        else:
            assert config["reduced"][key] == [value, config[key]], key
    # the cut: eleven consecutive published layers, in the published
    # ratio (8 : 40 : 40), a quarter of the experts and of the vocabulary
    published = row["config"]["hybrid_override_pattern"]
    assert published[25:36] == config["hybrid_override_pattern"] \
        == "*EMEMEMEMEM"
    assert len(published) == 88 == 8 * config["num_hidden_layers"]
    assert [published.count(c) for c in "*EM"] == [8, 40, 40]
    assert config["n_routed_experts"] * 4 == row["config"]["n_routed_experts"]
    assert config["vocab_size"] * 4 == row["config"]["vocab_size"]
    b = info["block"]
    for c in (config, twin()):
        check_consistent(c, b)
        arch = c["transformer_config"]
        # every published layer is a position of its own
        kinds, ffn = b.positions(c["hybrid_override_pattern"])
        assert (arch["layer_pattern"], arch["layer_ffn"]) == (kinds, ffn)
        assert arch["num_layers"] == c["num_hidden_layers"] == len(kinds)
        assert arch["moe_held_experts"][1] == c["n_routed_experts"]
        assert arch["rope_kinds"] == []         # assumed.no_rotary
    arch = config["transformer_config"]
    assert arch["moe_num_experts"] == row["config"]["n_routed_experts"]
    assert arch["moe_held_experts"] == [0, 128]
    assert (arch["moe_score_func"], arch["moe_select_bias"],
            arch["moe_shared_gate"]) == ("sigmoid", True, False)
    assert arch["vocab_size"] == 32768 and arch["max_seq_len"] == 8192
    assert arch["mamba_num_heads"] * arch["mamba_head_dim"] \
        == config["expand"] * config["hidden_size"]
    for key in ("weights", "no_rotary", "initialisation", "state_dtype",
                "gated_norm", "router", "positions_run", "left_out"):
        assert config["assumed"][key]
    engine = config["engine"]
    assert (engine["kv_block_size"], engine["max_ragged_sequence_count"],
            engine["compile_ahead"]) == (64, 32, 6)
    assert config["check"]["max_prompt_tokens"] \
        >= info["traffic"]["prompt_tokens"]["max"]
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
    assert b.layer_kinds(arch) == {"full": 1, "mamba2": 5, "ffn": 5}
    assert b.mamba_matmul_params(arch) / M == pytest.approx(109.6, abs=0.1)
    assert b.attention_matmul_params(arch) / M == pytest.approx(35.65,
                                                                abs=0.01)
    assert b.expert_matmul_params(arch) / M == pytest.approx(5.505, abs=1e-3)
    assert b.ffn_fixed_matmul_params(arch) / M == pytest.approx(54.5, abs=0.1)
    # a token's matmuls here: 5.5 of its 22 experts are held on average
    assert b.matmul_params(arch) / M == pytest.approx(
        5 * 109.6 + 35.65 + 5 * (54.5 + 5.5 * 5.505) + 134.2, abs=1)
    # the published model from the same functions: 120.7 B whole, 12.2 B
    # a token (gated experts would make it 169 B)
    whole = (40 * b.mamba_matmul_params(arch)
             + 8 * b.attention_matmul_params(arch)
             + 40 * (b.ffn_fixed_matmul_params(arch)
                     + 512 * b.expert_matmul_params(arch))
             + 2 * 131072 * 4096)
    assert whole / 1e9 == pytest.approx(120.7, abs=0.1)
    active = whole - 40 * 490 * b.expert_matmul_params(arch) - 131072 * 4096
    assert active / 1e9 == pytest.approx(12.2, abs=0.05)
    # what the program's model holds: 4,648.2 M parameters, 9.30 GB
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models import hybrid
    from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig

    cfg = TransformerConfig(**dict(arch, dtype=jnp.bfloat16))
    shapes = jax.eval_shape(CausalLM(cfg).init, jax.random.PRNGKey(0))
    total = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert total / M == pytest.approx(4648.2, abs=0.5)
    assert 2 * total / 1e9 == pytest.approx(9.30, abs=0.01)
    # the state: 128 x 64 x 128 float32 a layer a sequence = 4.19 MB and
    # 3 x 10,240 bf16 of conv tail; five layers, 33 slots: 0.70 GB
    assert b.ssm_state_bytes(arch) == 128 * 64 * 128 * 4 == 4194304
    assert b.conv_tail_bytes(arch) == 3 * 10240 * 2
    state = hybrid.state_shapes(cfg, 33)
    assert state == {"mamba_ssm": ((5, 33, 128, 64, 128), jnp.float32),
                     "mamba_conv": ((5, 33, 3, 10240), jnp.bfloat16)}
    per_seq = 5 * (b.ssm_state_bytes(arch) + b.conv_tail_bytes(arch))
    assert per_seq / M == pytest.approx(21.3, abs=0.05)
    assert 33 * per_seq / 1e9 == pytest.approx(0.70, abs=0.005)
    # the pool: one attention layer, 1 KB a token; 4,096 blocks of 64 hold
    # 32 sequences of 8k: 0.27 GB
    assert b.kv_token_bytes(arch) == 1024
    engine = info["config"]["engine"]
    tokens = engine["kv_blocks"] * engine["kv_block_size"]
    assert tokens == 32 * arch["max_seq_len"]
    assert tokens * b.kv_token_bytes(arch) / 1e9 == pytest.approx(0.27,
                                                                  abs=0.005)
    assert cfg.kv_groups() == ((0, 1),)
    assert (cfg.num_sparse_layers, cfg.num_linear_layers,
            cfg.num_attn_layers) == (5, 5, 1)
    assert (2 * total + 33 * per_seq + tokens * 1024) / 1e9 \
        == pytest.approx(10.27, abs=0.01)


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
    want = np.asarray(block().logits(
        params, np.asarray(tokens, np.int32), arch, 16))
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
    """The prompt served once, with what the engine counted."""
    arch, model, params, tokens, _ = tiny
    engine = _engine(model, params)
    sm = engine.state_manager
    shapes = {k: v.shape for k, v in sm.forward_cache.items()}
    logits = _served(engine, tokens)
    last = dict(engine.last_put)
    occupancy = sm.occupancy()
    engine.flush(7)
    return dict(
        logits=logits, totals=dict(engine.put_totals), shapes=shapes,
        last=last, occupancy=occupancy,
        free=(sm.allocator.free_blocks, sm.free_state_slots,
              len(sm._free_id_slots)),
        total=(sm.allocator.total_blocks, sm.state_slots, sm.id_slots))


def test_chunks_then_decode_through_the_pool_and_the_slots(tiny, run):
    """Logits, not sampled ids, at float32: the limit of 1e-4 of range is
    the twins' ``check`` tolerance, fifty times what two float32
    implementations of these equations differ by here (2e-7 of range:
    the chunked form against the token-by-token scan, the grouped matmul
    against the loop over experts) and a tenth of the least any fault
    below moves them."""
    arch, *_, want = tiny
    # one attention layer's k and v; five Mamba-2 layers' state and conv
    # tail, a slot a sequence and a scratch
    assert run["shapes"] == {"k": (1, 128, 2, 8, 16), "v": (1, 128, 2, 8, 16),
                             "mamba_ssm": (5, 5, 8, 16, 16),
                             "mamba_conv": (5, 5, 3, 192)}
    assert _worst(run["logits"], want) < 2e-6 < 1e-4
    totals = run["totals"]
    n = PROMPT + STEPS
    assert totals["tokens_valid"] == n
    assert totals["ssm_chunk_tokens"] == PROMPT
    assert totals["ssm_rows_stepped"] == STEPS
    state = block().ssm_state_bytes(arch)
    assert state == 8 * 16 * 16 * 4
    # four chunk forwards and six steps, a row each, five layers, read
    # and written
    assert totals["ssm_state_bytes"] == (4 + STEPS) * 5 * 2 * state
    assert run["last"]["ssm_state_bytes"] == 5 * 2 * state
    # five of the eleven positions carry an FFN: top-4 of 16, 4 held
    assert totals["moe_rows_routed"] == n * 4 * 5
    assert totals["moe_rows_held"] == sum(
        t * 20 * 4 // 16 for t in (32, 32, 32, 4) + (1,) * STEPS)
    leaves = run["occupancy"]["leaf_bytes"]
    assert leaves["mamba_ssm"] == 5 * 5 * state
    assert leaves["mamba_conv"] == 5 * 5 * 3 * 192 * 4
    assert run["occupancy"]["bytes_total"] == leaves["k"] + leaves["v"]


def test_another_chunking_and_a_second_sequence_agree(tiny, run):
    arch, model, params, tokens, want = tiny
    span = want.max() - want.min()
    # chunks of 24: a tile and a half, the state handed on inside a tile
    engine = _engine(model, params)
    other = _served(engine, tokens, chunk=24)
    assert np.abs(other - run["logits"]).max() < 2e-6 * span
    # a second sequence on the slot beside it starts from zero
    again = _served(engine, tokens, uid=8)
    assert np.abs(again - run["logits"]).max() < 2e-6 * span
    sm = engine.state_manager
    assert sm.state_slots - sm.free_state_slots == 2


def test_every_block_and_slot_comes_back(run):
    assert run["free"] == run["total"]
    assert run["occupancy"]["state_slots_used"] == 1


def test_the_four_shares_and_the_shared_expert_add_up_to_the_uncut_layer(
        tiny):
    """What ties the share to the model: a LatentMoE layer with all 16
    experts against the four chips' shares of four experts each — every
    share's partial sum through ``W_l2``, the shared expert counted once
    — on the same normed input. The cut's reference (and the program)
    computes one such share plus the shared expert."""
    import jax
    import jax.numpy as jnp

    arch, _, params, tokens, _ = tiny
    b = block()
    n = arch["moe_held_experts"][1]
    assert 4 * n == arch["moe_num_experts"]
    held = jax.tree.map(lambda a: a[0], params["layers"]["slot1"])
    keys = jax.random.split(jax.random.PRNGKey(11), 2)
    # the uncut layer's experts: this share's in their place, the other
    # twelve drawn here
    lo = arch["moe_held_experts"][0]
    w_in = jnp.std(held["w_in"]) * jax.random.normal(
        keys[0], (4 * n,) + held["w_in"].shape[1:])
    w_out = jnp.std(held["w_out"]) * jax.random.normal(
        keys[1], (4 * n,) + held["w_out"].shape[1:])
    w_in = w_in.at[lo:lo + n].set(held["w_in"])
    w_out = w_out.at[lo:lo + n].set(held["w_out"])
    u = jax.random.normal(jax.random.PRNGKey(12), (40, arch["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        whole = b.latent_moe(u, dict(held, w_in=w_in, w_out=w_out),
                             dict(arch, moe_held_experts=[0, 4 * n]))
        shares = [b.routed_part(
            u, dict(held, w_in=w_in[j * n:(j + 1) * n],
                    w_out=w_out[j * n:(j + 1) * n]),
            dict(arch, moe_held_experts=[j * n, n])) for j in range(4)]
        shared = b.shared_part(u, held)
        mine = b.latent_moe(u, held, arch)
    top = float(jnp.abs(whole).max())
    assert float(jnp.abs(sum(shares) + shared - whole).max()) < 2e-6 * top
    # this configuration's layer is its own share and the shared expert
    assert float(jnp.abs(shares[lo // n] + shared - mine).max()) < 2e-6 * top
    # the routed parts alone, held to their own size (the shared expert,
    # on the full width, is louder than all four at the seed's scale):
    # the shares add up to the uncut routed sum, and every share carries
    # weight — none is the whole, none is nothing
    with jax.default_matmul_precision("highest"):
        routed = b.routed_part(u, dict(held, w_in=w_in, w_out=w_out),
                               dict(arch, moe_held_experts=[0, 4 * n]))
    loud = float(jnp.abs(routed).max())
    assert float(jnp.abs(sum(shares) - routed).max()) < 2e-6 * loud
    for s in shares:
        assert 0.05 * loud < float(jnp.abs(s).max()) < loud
    # a token's 4 picks land on 4 different experts, its weights sum to
    # the routed scale
    w, e = b.route(u, held, arch)
    assert np.allclose(np.asarray(w).sum(-1), arch["moe_route_scale"])
    assert all(len(set(row)) == arch["moe_top_k"] for row in np.asarray(e))


LOUD = 4.0
_QUIET_LEAVES = ("mamba_A_log", "mamba_D", "mamba_dt_bias", "mamba_conv_b",
                 "mamba_conv_w")


@pytest.fixture(scope="module")
def loud(tiny):
    """The twin's weights with every projection of its layers ``LOUD``
    times as large and the selection bias ten times, and the reference's
    answer on them. At the seed's own scale (0.02 at a width of 64) the
    FFNs and the one attention layer move the logits by 1e-5 of range or
    less — ``relu(x)²`` squares what is small already — so a routed scale
    dropped or a rotation added would pass any limit a float32
    comparison can hold; four times as loud each switch below shows by
    1e-3 of range or more while program and reference still agree to
    2e-6."""
    import jax
    import jax.numpy as jnp

    arch, model, params, tokens, _ = tiny

    def louder(name, a):
        if name == "router_b":
            return 10.0 * a
        if a.ndim < 3 or name.endswith("norm_w") or name in _QUIET_LEAVES:
            return a
        return LOUD * a

    params = dict(params, layers={
        slot: {name: louder(name, a) for name, a in lp.items()}
        for slot, lp in params["layers"].items()})
    want = np.asarray(block().logits(
        params, np.asarray(tokens, np.int32), arch, 16))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(model.apply)(
            params, jnp.asarray(tokens)[None]))[0, PROMPT - 1:]
    assert _worst(got, want) < 2e-6
    return params, want


def _changed(tiny, params, **change):
    """The program's logits under an architecture that differs by
    ``change``, on ``params``."""
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
    {"rope_kinds": None}, {"moe_route_scale": 1.0}, {"moe_norm_topk": False},
    {"moe_select_bias": False}, {"norm_eps": 1e-2}],
    ids=lambda c: next(iter(c)))
def test_a_switch_thrown_the_other_way_fails(tiny, loud, change):
    """The same weights under an architecture that differs in one switch —
    the attention layer rotated, the routed scale dropped, the weights not
    renormalised, the selection bias ignored, another epsilon — against
    the reference: the comparison that passes at 2e-6 fails by orders."""
    params, want = loud
    if not change.get("moe_select_bias", True):
        params = dict(params, layers={
            k: {n: a for n, a in v.items() if n != "router_b"}
            for k, v in params["layers"].items()})
    assert _worst(_changed(tiny, params, **change), want) > 1e-3


@pytest.mark.parametrize("leaf", ["mamba_D", "mamba_conv_b", "mamba_dt_bias"])
def test_a_leaf_of_the_state_space_layer_left_out_fails(tiny, loud, leaf):
    """What a look-alike of the layer lacks — the skip ``D x``, the
    conv's bias, the step's bias — set to zero in the program's weights
    and not in the reference's: the comparison reads each."""
    import jax.numpy as jnp

    params, want = loud
    params = dict(params, layers={
        k: {n: jnp.zeros_like(a) if n == leaf else a for n, a in v.items()}
        for k, v in params["layers"].items()})
    assert _worst(_changed(tiny, params), want) > 1e-3


def test_each_refused_feature_raises_its_own_error_and_the_rest_works(tiny):
    from deepspeed_tpu.models.hybrid import RecurrentStateUnsupported

    arch, model, params, tokens, _ = tiny
    with pytest.raises(RecurrentStateUnsupported, match="prefix cache"):
        _engine(model, params, enable_prefix_cache=True)
    engine = _engine(model, params)
    engine.put([1], [tokens[:32]])
    with pytest.raises(RecurrentStateUnsupported, match="trim_sequence"):
        engine.trim_sequence(1, 2)
    with pytest.raises(RecurrentStateUnsupported, match="KV tier"):
        engine.configure_kv_tier(True)
    # multi-token prediction is left out: a hybrid forward verifies nothing
    with pytest.raises(RecurrentStateUnsupported, match="verif"):
        engine.put([2], [tokens[:8]], verify_width=4)
    engine.put([1], [tokens[32:50]])
    engine.put([1, 3], [[tokens[50]], tokens[:20]])
    for uid in (1, 2, 3):
        engine.flush(uid)
    sm = engine.state_manager
    assert sm.allocator.free_blocks == sm.allocator.total_blocks
    assert sm.free_state_slots == sm.state_slots


# ------------------------------------------------------ scopes and readers

def test_the_new_scope_names_resolve_through_the_blocks_scopes():
    b = block()
    body = "jit(_forward)/layers/while/body/closed_call/"
    cases = {
        body + "mamba/mamba_proj/dot_general:": "mamba_proj",
        body + "mamba/mamba_conv/mul:": "mamba_conv",
        body + "mamba/mamba_scan/while/body/closed_call/dot_general:":
            "mamba_scan",
        body + "mamba/mamba_scan/reduce_sum:": "mamba_scan",
        body + "mamba/mamba_out/dot_general:": "mamba_out",
        body + "mamba/mamba_state_io/scatter:": "mamba_state_io",
        body + "mamba/mamba_state_io/gather:": "mamba_state_io",
        body + "mamba/add:": "mamba",
        body + "mlp/latent_proj/dot_general:": "latent_proj",
        body + "mlp/router/dot_general:": "router",
        body + "mlp/experts/gmm/pallas_call:": "experts",
        body + "mlp/shared_expert/dot_general:": "shared_expert",
        body + "mlp/add:": "mlp",
        body + "full_attn/qkv/dot_general:": "qkv",
        body + "full_attn/attend/paged_attention/pallas_call:": "attend",
    }
    for op_name, want in cases.items():
        assert scopes.scope_of(op_name, b.SCOPES) == want, op_name
    assert set(b.MAMBA_SCOPES) < set(b.SCOPES)


def test_the_programs_forward_carries_the_scopes(tiny):
    """The names above are the program's: the twin's chunk forward, as
    lowered, holds every one of them."""
    import jax.numpy as jnp

    arch, model, params, *_ = tiny
    engine = _engine(model, params)
    sm = engine.state_manager
    text = engine.paged.forward.lower(
        engine.params, sm.forward_cache, jnp.zeros((1, 32), jnp.int32),
        jnp.zeros((1,), jnp.int32), jnp.full((1,), 32, jnp.int32),
        jnp.zeros((1, 64), jnp.int32), jnp.zeros((1,), jnp.int32)
    ).compile().as_text()
    for name in block().SCOPES:
        assert f"/{name}/" in text or f"/{name}\"" in text, name


class _Ctx:
    """A hand-made context: the block and the program's ``forward``
    spans; ``trace`` is only there or not."""

    def __init__(self, records, traced=True):
        _, info = real()
        self.info = info
        self.device = {"kind": "TPU v5 lite"}
        self.result = {
            "arch": info["config"]["transformer_config"],
            "window": (0.0, 100.0), "trace_marks": (10.0, 100.0),
            "program_spans": [{"name": "forward", "t_start": 5.0 + 10 * i,
                               "attrs": r} for i, r in enumerate(records)]}
        self.trace = {} if traced else None


def test_the_new_readers_on_hand_made_contexts(monkeypatch):
    state = 5 * 2 * 4194304
    step = lambda rows: {"bucket_chunk": 1, "ssm_rows_stepped": rows,  # noqa
                         "ssm_chunk_tokens": 0,
                         "ssm_state_bytes": rows * state}
    chunk = {"bucket_chunk": 512, "ssm_rows_stepped": 0,
             "ssm_chunk_tokens": 300, "ssm_state_bytes": state}
    # the first forward began before the marks: not read
    ctx = _Ctx([step(32), step(20), chunk, step(24), step(28)])
    assert [a["ssm_rows_stepped"] for a in ssm_readers.stepped_forwards(ctx)
            ] == [20, 24, 28]
    # 24 rows the median forward, 1.0065 GB, in 2.5 ms under mamba_scan
    # and 7.5 under mamba_state_io: the rate is over both
    spent = {("mamba_scan", False): 2.5, ("mamba_state_io", False): 7.5}
    monkeypatch.setattr(ssm_readers.hybrid_readers, "scope_ms_per_forward",
                        lambda ctx, scope, mixed: spent.get((scope, mixed)))
    assert ssm_readers.state_gbps(ctx) == pytest.approx(
        24 * state / 0.010 / 1e9)
    assert 90 < ssm_readers.state_gbps(ctx) < 819
    # a program without the gather's scope: nothing, not the scan's rate
    del spent[("mamba_state_io", False)]
    assert ssm_readers.state_gbps(ctx) is None
    spent[("mamba_state_io", False)] = 7.5
    # nothing to read: an untraced run, the parent's spans, a window of
    # chunk forwards only, no device time under the scope
    assert ssm_readers.state_gbps(_Ctx([step(4), step(4)],
                                       traced=False)) is None
    parent = {"bucket_chunk": 1, "valid_tokens": 4}
    assert ssm_readers.state_gbps(_Ctx([parent, parent, parent])) is None
    assert ssm_readers.state_gbps(_Ctx([chunk, chunk, chunk])) is None
    monkeypatch.setattr(ssm_readers.hybrid_readers, "scope_ms_per_forward",
                        lambda ctx, scope, mixed: None)
    assert ssm_readers.state_gbps(ctx) is None
    for reader in (ssm_readers.mamba_share, ssm_readers.latent_share):
        assert reader(_Ctx([step(4)], traced=False)) is None
    for name in NEW_READERS:
        module = mf.find_module(mf.HERE, "layer_metrics", name)
        assert module.reduce(_Ctx([step(4)], traced=False)) is None


# ----------------------------------------------------------- the rehearsal

@pytest.mark.parametrize("traced", [1])
def test_the_cell_rehearsed_on_the_cpu(checkout, capsys, traced):  # noqa: F811
    """The whole runner over the engine at the tiny twin's size, under
    the real names (the twin and its mix reach the checkout through
    ``tests/conftest.py``, found by name): prompts in chunks beside
    decoding rows, the pool and the state slots, the logits check against
    this block's reference, every block and slot back. Traced only: the
    untraced line is the harness's own, held by the other cells'
    rehearsals."""
    path = os.path.join(checkout, "benchmark/workloads", CELL + ".json")
    _write(path, dict(_read(path), rate_rps=10.0, trace_s=1.0))
    manifest = mf.load(checkout)
    mf.validate(manifest, checkout)
    info = mf.resolve(manifest, CELL, checkout)
    assert info["config"] == twin()
    assert info["traffic"] == _read(os.path.join(TWINS, "traffic",
                                                 "reason.json"))
    line, extra = rehearse(checkout, capsys, CELL, traced)
    assert line["correct"], extra["why_not"]
    assert line["failed"] == 0 and line["attempted"] > 5
    check_line(line, manifest, CELL, "per_layer")
    check = extra["counters"]["logits_check"]
    assert 0 < check["max_rel_err"] < 1e-4 and check["rms_rel_err"] < 1e-4
    assert extra["counters"]["state_slots_held"] == 0
    # off the chip the counters and the spans are read, the device is not
    assert {"batch_seqs_mean", "pad_ratio"} <= set(line["metrics"]), \
        sorted(line["metrics"])
    assert not set(NEW_READERS) & set(line["metrics"])
    assert not {"experts_share", "moe_route_share", "fwd_decode_dev_ms"} \
        & set(line["metrics"])
