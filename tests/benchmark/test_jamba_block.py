"""The ``jamba`` block as the benchmark finds it: the manifest with its
entries, the configuration against the catalog row it was drawn from
(nothing reduced), the issue's arithmetic, the reference against the
program's model at the tiny twin's size (``CausalLM.apply``, and prefill
in chunks then decode through the pool and the slots, the K/V rows read
back through the block table and the probe's short sequence in a slot of
its own), a switch thrown the other way failing, the scope names, the
three S6 readers on hand-made contexts, the controls — the block's own
two and ``controls.py``'s — and the cell rehearsed end to end on the CPU
under the real names. The twin keeps the published order at all 28 layers
(S6 x 7, attention, S6 x 13, attention, S6 x 6) and seats 40.

One module-scoped fixture builds the twin's model, weights, reference
logits and engine once."""

import json
import os

import numpy as np
import pytest
from test_benchmark_runners import (_read, _write, check_line,  # noqa: F401
                                    checkout, rehearse)

from benchmark import manifest as mf
from benchmark import s6_readers, scopes
from benchmark.model import check_consistent

CELL, CONFIG = "ai21-jamba2-3b.chatrate", "ai21-jamba2-3b"
NEW_READERS = ("s6_step_roofline", "s6_scan_us_per_token",
               "mamba_norm_share")
LISTED = ("attn_full_share", "logits_share", "kv_blocks_peak_share",
          "state_slots_peak_share", "mamba_share", "mamba_scan_share",
          "mamba_state_io_share", "ssm_state_gbps",
          "paged_attn_hybrid_roofline", "paged_unmasked_turn_share",
          "setup_trace_s", "setup_lower_s", "setup_compile_s",
          "setup_build_wall_s", "setup_gc_s", "setup_cache_hit_share",
          "batch_seqs_mean", "pad_ratio", "fwd_decode_dev_ms",
          "fwd_mixed_dev_ms", "dev_decode_ms_per_forward",
          "dev_prefill_us_per_token", "decode_time_chunk_share",
          "queue_wait_p50_ms", "gen_late_p99_ms", "host_step_share",
          "step_pack_ms", "idle_launch_share", "prefill_own_share")
CATALOG_FILE = "/opt/skills/guides/model-configs/architectures.jsonl"
CATALOG_NAME = "AI21-Jamba2-3B"
TWINS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "twins")
PROMPT, STEPS = 90, 5


def block():
    return mf.find_module(mf.HERE, "blocks", "jamba")


def real():
    manifest = mf.load()
    return manifest, mf.resolve(manifest, CELL)


def twin():
    return _read(os.path.join(TWINS, "configs", CONFIG + ".json"))


# ------------------------------------------------------------ the manifest

def test_the_manifest_validates_with_the_new_entries():
    manifest, info = real()
    mf.validate(manifest)
    assert len(manifest["workloads"]) >= 13
    assert info["block"].__name__.endswith("jamba")
    traffic = info["traffic"]
    assert (traffic["generator"], traffic["loop"],
            traffic["schedule_seed"]) == ("stratified", "open", 0)
    assert traffic["prompt_tokens"] == {
        "median": 256, "sigma": 1.0, "min": 32, "max": 16384}
    assert traffic["output_tokens"] == {
        "median": 512, "sigma": 0.6, "min": 64, "max": 2048}
    assert traffic["preroll_s"] == 30
    assert info["cell"]["chips"] == 1 and info["workload"]["serving"] == {}
    assert 0 < info["workload"]["rate_rps"]
    ends = {m["name"] for m in mf.metrics_for(manifest, "end_to_end", CELL)}
    assert ends == {"ttft_p90_ms", "tpot_p90_ms", "setup_s"}
    mine = {m["name"] for m in mf.metrics_for(manifest, "per_layer", CELL)}
    assert set(NEW_READERS) | set(LISTED) <= mine
    # the shares of a roofline: the S6 step's, new, and the paged kernel's
    # with the block's own count of attention layers (two of 28 —
    # ``paged_attn_roofline`` would count a call a layer, fourteen times
    # the work, and read over 100)
    assert {m for m in mine if "roofline" in m or m == "mfu"} \
        == {"s6_step_roofline", "paged_attn_hybrid_roofline"}
    assert not mine & {"experts_share", "moe_route_share", "gdn_share",
                       "attn_window_share", "cross_attn_share", "gmu_share",
                       "kv_window_blocks_peak_share", "xdec_rows_share",
                       # one layer group that hands no block back: the
                       # engine keeps no record by group
                       "kv_resident_ratio", "kv_full_blocks_peak_share"}
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert all(by_name[name]["moves"] in ends for name in mine)
    for name in NEW_READERS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["source"] == "device_trace"
    assert by_name["s6_scan_us_per_token"]["moves"] == "ttft_p90_ms"
    assert {by_name[n]["moves"] for n in ("s6_step_roofline",
                                          "mamba_norm_share")} \
        == {"tpot_p90_ms"}
    at = lambda group, name: [e["name"] for e in manifest[group]  # noqa: E731
                              ].index(name)
    assert at("configs", CONFIG) > at("configs", "phi-4-mini-flash-reasoning")
    assert at("workloads", CELL) > at(
        "workloads", "phi-4-mini-flash-reasoning.deepthink")
    assert at("per_layer", "s6_step_roofline") > at(
        "per_layer", "paged_attn_diff_roofline")
    # every serving configuration before this one seats 32
    seats = {c["name"]: _read(os.path.join(mf.CHECKOUT, c["file"]))
             .get("engine", {}).get("max_ragged_sequence_count")
             for c in manifest["configs"]}
    assert seats.pop(CONFIG) == 128
    assert set(seats.values()) <= {32, None}


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
        assert arch["layer_runs"] == b.jamba_layer_runs(
            c["num_hidden_layers"], c["attn_layer_period"],
            c["attn_layer_offset"])
        assert arch["num_layers"] == c["num_hidden_layers"]
        assert arch["head_size"] * arch["num_heads"] == arch["hidden_size"]
        assert arch["mamba1_inner_size"] \
            == c["mamba_expand"] * c["hidden_size"]
        assert arch["rope_kinds"] == []         # assumed.no_position_term
        assert (arch["norm"], arch["use_bias"], arch["tie_embeddings"],
                arch["mamba1_inner_norm"]) == ("rmsnorm", False, True, True)
        assert "attn_scale" not in arch and "sliding_window" not in arch
    arch = config["transformer_config"]
    assert arch["layer_runs"] == [[["mamba1"], 7], [["full"], 1],
                                  [["mamba1"], 13], [["full"], 1],
                                  [["mamba1"], 6]]
    kinds = b.layer_kinds_in_order(28, 14, 7)
    assert [i for i, k in enumerate(kinds) if k == "full"] == [7, 21]
    assert (arch["mamba1_inner_size"], arch["mamba1_state_size"],
            arch["mamba1_dt_rank"], arch["mamba1_conv_kernel"]) == (
                5120, 16, 160, 4)
    for key in ("_provenance", "weights", "layer_layout", "s6_sizes",
                "s6_form", "state_dtype", "attention", "no_position_term",
                "norms", "initialisation", "positions_run", "left_out"):
        assert config["assumed"][key], key
    assert "whole" in config["deployment"]
    engine, check = config["engine"], config["check"]
    assert arch["max_seq_len"] == 18432 == 288 * engine["kv_block_size"]
    assert engine["kv_blocks"] == 128 * 288
    assert engine["max_ragged_sequence_count"] == 128
    # every checked prompt is two of the replay's chunks at least, and the
    # cell's fixed schedule holds such prompts (the longest of each block
    # of sixteen: 1,649 tokens)
    assert check["min_prompt_tokens"] > b.REPLAY_CHUNK
    assert check["min_prompt_tokens"] < check["max_prompt_tokens"]
    import itertools

    gen = mf.find_module(mf.HERE, "traffic", "stratified")
    block16 = [len(r.prompt) for r in itertools.islice(
        gen.requests(info["traffic"], 64, 1, rate_rps=1.0), 16)]
    assert max(block16) == 1649
    assert engine["max_chunk_tokens"] == b.REPLAY_CHUNK == 1024
    assert sum(check["min_prompt_tokens"] <= n <= check["max_prompt_tokens"]
               for n in block16) == 1
    assert check["tolerance"] < 0.3 and check["rms_tolerance"] < 0.3
    tw = twin()
    assert set(tw["transformer_config"]) == set(arch)
    assert all(tw["transformer_config"][k] == arch[k] for k in arch
               if isinstance(arch[k], (bool, str, list)) and k != "dtype")
    assert tw["engine"]["max_ragged_sequence_count"] > 32


def test_the_arithmetic_is_the_issues():
    _, info = real()
    b, arch = info["block"], info["config"]["transformer_config"]
    M = 1e6
    assert b.layer_kinds(arch) == {"mamba1": 26, "full": 2}
    assert b.attention_layers(arch) == 2
    mixers = b.mixer_matmul_params(arch)
    assert mixers["mamba1"] / M == pytest.approx(41.12, abs=0.02)
    assert mixers["full"] / M == pytest.approx(13.76, abs=0.01)
    assert b.matmul_params(arch) / M == pytest.approx(
        26 * (41.12 + 62.91) + 2 * (13.76 + 62.91) + 167.77, abs=1)
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models import hybrid
    from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig

    cfg = TransformerConfig(**dict(arch, dtype=jnp.bfloat16))
    shapes = jax.eval_shape(CausalLM(cfg).init, jax.random.PRNGKey(0))
    total = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert total / M == pytest.approx(3029.3, abs=0.1)
    assert 2 * total / 1e9 == pytest.approx(6.06, abs=0.01)
    lp = shapes["layers"]["run0_slot0"]
    assert (lp["mamba1_dt_norm"].shape, lp["mamba1_b_norm"].shape,
            lp["mamba1_c_norm"].shape) == ((7, 160), (7, 16), (7, 16))
    # one K/V head under twenty query heads, two layers: 1 KiB a token
    assert cfg.kv_groups() == ((0, 2),)
    assert cfg.kv_layouts(64) == ({"k": (1, 64, 128), "v": (1, 64, 128)},)
    assert b.kv_token_bytes(arch) == 1024
    assert (cfg.num_linear_layers, cfg.num_attn_layers) == (26, 2)
    assert cfg.exit_at() is None
    assert b.ssm_state_bytes(arch) == 16 * 5120 * 4 == 327680
    assert hybrid.state_shapes(cfg, 129) == {
        "mamba1_ssm": ((26, 129, 16, 5120), jnp.float32),
        "mamba1_conv": ((26, 129, 3, 5120), jnp.bfloat16)}
    assert b.seat_bytes(arch) / M == pytest.approx(9.32, abs=0.005)
    engine = info["config"]["engine"]
    pool = engine["kv_blocks"] * 64 * 1024
    assert pool / 1e9 == pytest.approx(2.42, abs=0.005)
    resident = 2 * total + 129 * b.seat_bytes(arch) + pool
    assert resident / 1e9 == pytest.approx(9.7, abs=0.05)
    # the program's count of a step's state traffic is the reader's least
    # work: 2 x rows x 26 x 327,680
    from deepspeed_tpu.models.mixers import mamba1

    staged = [(None, [0])] * 70
    assert mamba1.count(cfg, staged, 1, 64) == {
        "ssm_rows_stepped": 70, "ssm_chunk_tokens": 0,
        "ssm_state_bytes": 2 * 70 * 26 * 327680}
    assert mamba1.count(cfg, [(None, [0] * 300)], 512, 64) == {
        "ssm_rows_stepped": 0, "ssm_chunk_tokens": 300,
        "ssm_state_bytes": 2 * 26 * 327680}
    cost = b.paged_attention_cost(arch, 3, 7000, 7000)
    assert cost["flops"] == 7000 * 20 * 128 * 4
    assert cost["bytes"] == 7000 * 512 + 3 * 20 * 128 * 2 * 2


# ----------------------------------------- the reference and the program

@pytest.fixture(scope="module")
def tiny():
    """The twin's model, seeded weights, a token list, the reference's
    logits for it and the engine, built once for the module."""
    import jax.numpy as jnp

    from benchmark.model import seeded_params
    from deepspeed_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2, RaggedInferenceEngineConfig)
    from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig

    arch = twin()["transformer_config"]
    model = CausalLM(TransformerConfig(**dict(arch, dtype=jnp.float32)))
    params = seeded_params(model, 3, jnp.float32)
    tokens = np.random.default_rng(4).integers(
        0, arch["vocab_size"], size=PROMPT + STEPS).tolist()
    # (the K/V rows behind the logits are the replay's: below)
    want = np.asarray(block().logits(
        params, np.asarray(tokens, np.int32), arch, 16))
    engine = InferenceEngineV2(
        model, params=params, config=RaggedInferenceEngineConfig(**dict(
            twin()["engine"], compile_ahead=0)))
    return arch, model, params, tokens, want, engine


def _worst(got, want):
    want = want[:PROMPT + STEPS]
    return np.abs(got - want[PROMPT - 1:]).max() / (want.max() - want.min())


def test_reference_agrees_with_the_programs_model(tiny):
    import jax
    import jax.numpy as jnp

    arch, model, params, tokens, want, _ = tiny
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(model.apply)(
            params, jnp.asarray(tokens)[None]))[0]
    want = want[:len(tokens)]
    assert np.abs(got - want).max() < 2e-6 * (want.max() - want.min())
    ids = np.asarray([tokens + tokens[:1]], np.int32)
    logp = jax.nn.log_softmax(got)
    nll = -float(jnp.mean(jnp.take_along_axis(
        logp, jnp.asarray(ids[0, 1:])[:, None], -1)))
    assert float(block().loss(params, ids, arch, q_block=16)) \
        == pytest.approx(nll, rel=1e-5)


def test_the_replay_through_the_pool_the_slots_and_the_probe(tiny):
    """The replay's view against the reference: the prompt in chunks
    of 32 (state carried across two chunk boundaries) and five greedy
    steps, the two attention layers' K and V of every ``KV_STRIDE``-th
    position as the pool holds them under the sequence's block table, and
    the probe's row; every block and slot back afterwards."""
    arch, model, params, tokens, want, engine = tiny
    b = block()
    before = dict(engine.put_totals)
    (seen, rows, got), = b.replay(engine, 9, tokens[:PROMPT], STEPS)
    engine.flush(9)
    last, probe = rows.pop(), got.pop()
    assert seen[:PROMPT] == tokens[:PROMPT] and len(seen) == PROMPT + STEPS
    n_kv = -(-(PROMPT + STEPS) // b.KV_STRIDE)
    assert rows[:STEPS + 1] == list(range(PROMPT - 1, PROMPT + STEPS))
    assert rows[STEPS + 1:] == [-1 - j for j in range(n_kv)]
    full = np.asarray(b.logits(params, np.asarray(seen, np.int32), arch, 16))
    assert full.shape[0] == len(seen) + n_kv
    for row, g in zip(rows, got):
        assert np.abs(g - full[row]).max() \
            < 5e-6 * (full[row].max() - full[row].min())
    kv = full[-1]       # position 0: two layers' K then V of 16, then zeros
    assert np.abs(kv[:64]).min() > 0 and not kv[64:].any()
    # the probe: the prompt's first chunk alone, its last row
    assert last == 31
    assert np.abs(probe - want[31]).max() \
        < 5e-6 * (want[31].max() - want[31].min())
    totals = {k: engine.put_totals[k] - before.get(k, 0)
              for k in engine.put_totals}
    assert totals["ssm_rows_stepped"] == STEPS
    assert totals["ssm_chunk_tokens"] == PROMPT + 32
    # 26 layers' state of 4 x 128 float32, read and written a row a forward
    assert totals["ssm_state_bytes"] == 2 * (3 + STEPS + 1) * 26 * 4 * 128 * 4
    sm = engine.state_manager
    assert sm.allocator.free_blocks == sm.allocator.total_blocks
    assert sm.free_state_slots == sm.state_slots == 40


@pytest.mark.parametrize("change", [
    {"mamba1_inner_norm": False}, {"norm_eps": 1e-2},
    {"leaf": "mamba1_c_norm"}], ids=lambda c: str(next(iter(c.values()))))
def test_a_switch_thrown_the_other_way_fails(tiny, change):
    """The norms inside left out, another epsilon, a gain set to zero in
    the program's weights and not in the reference's."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig

    arch, _, params, tokens, want, _ = tiny
    leaf = change.pop("leaf", None)
    if leaf:
        params = dict(params, layers={
            k: {n: jnp.zeros_like(a) if n == leaf else a
                for n, a in v.items()} for k, v in params["layers"].items()})
    other = CausalLM(TransformerConfig(**dict(arch, dtype=jnp.float32,
                                              **change)))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(other.apply)(
            params, jnp.asarray(tokens)[None]))[0, PROMPT - 1:]
    assert _worst(got, want) > 1e-3


# ------------------------------------------------------ scopes and readers

def test_the_scope_names_resolve_and_the_forward_carries_them(tiny):
    import jax.numpy as jnp

    b = block()
    body = "jit(_forward)/layers/while/body/closed_call/"
    cases = {
        body + "mamba/mamba_norm/mul:": "mamba_norm",
        body + "mamba/mamba_proj/dot_general:": "mamba_proj",
        body + "mamba/mamba_scan/mul:": "mamba_scan",
        body + "mamba/mamba_state_io/gather:": "mamba_state_io",
        body + "mlp/dense_mlp/dot_general:": "dense_mlp",
        "jit(_forward)/layers/full_attn/attend/pallas_call:": "attend",
        "jit(_forward)/logits/dot_general:": "logits",
    }
    for op_name, want in cases.items():
        assert scopes.scope_of(op_name, b.SCOPES) == want, op_name
    assert set(b.MAMBA_SCOPES) < set(b.SCOPES)
    assert b.ATTN_SCOPES == {"full": "full_attn"}
    engine = tiny[-1]
    sm = engine.state_manager
    text = engine.paged.forward.lower(
        engine.params, sm.forward_cache, jnp.zeros((40, 1), jnp.int32),
        jnp.zeros((40,), jnp.int32), jnp.ones((40,), jnp.int32),
        jnp.zeros((40, 64), jnp.int32), jnp.zeros((40,), jnp.int32)
    ).compile().as_text()
    for name in b.SCOPES:
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
    layer = 2 * 327680
    # forwards as ``dispatch_readers`` pairs them: an execution's interval
    # and its own dispatch's counts; the last of a kind is left out
    step = lambda k, rows: {"start": k, "end": k + 0.02,     # noqa: E731
                            "in_window": True,
                            "attrs": {"bucket_chunk": 1, "rows": rows,
                                      "valid_tokens": rows}}
    chunk = lambda k, n: {"start": k, "end": k + 0.05,       # noqa: E731
                          "in_window": True,
                          "attrs": {"bucket_chunk": 512, "rows": 1,
                                    "valid_tokens": n}}
    reduced = {"forwards": [step(0, 64), chunk(1, 300), step(2, 70),
                            chunk(3, 2048), step(4, 72), chunk(5, 500),
                            step(6, 80)]}
    from benchmark import dispatch_readers

    monkeypatch.setattr(dispatch_readers, "_reduced", lambda ctx: reduced)
    ctx = _Ctx([])
    assert [f["attrs"]["rows"] for f in s6_readers._forwards(ctx, False)] \
        == [64, 70, 72]
    assert [f["attrs"]["valid_tokens"]
            for f in s6_readers._forwards(ctx, True)] == [300, 2048]
    # the device's operations, by (start, innermost scope, self seconds)
    ops = [(0.001, "mamba/mamba_scan", 0.0012),
           (0.003, "mamba/mamba_state_io", 0.0018),
           (0.005, "mamba/mamba_norm", 0.0001), (0.006, "mlp", 0.006),
           (1.001, "mamba/mamba_scan", 0.020),
           (1.030, "mamba/mamba_proj", 0.010),
           (2.001, "mamba/mamba_state_io", 0.003),
           (3.001, "mamba/mamba_scan", 0.040),
           (4.001, "mamba/mamba_scan", 0.003),
           (5.001, "mamba/mamba_scan", 0.030),       # the last chunk: out
           (6.001, "mamba/mamba_state_io", 0.009)]   # the last step: out
    events = [{"line": "XLA Ops", "start": at, "dur": own, "plane": 0,
               "op_name": f"jit(_forward)/layers/{scope}/fusion:"}
              for at, scope, own in ops]
    from benchmark import trace

    assert trace.OPS_LINE == "XLA Ops"
    monkeypatch.setattr(scopes, "load", lambda path: events)
    ctx.result["xplane"] = "unused"
    # 64 + 70 + 72 rows' state once each way over 819 GB/s, in 3 + 3 + 3 ms
    least = (64 + 70 + 72) * 26 * layer / 819e9
    assert s6_readers.step_roofline(ctx) == pytest.approx(
        100 * least / 0.009)
    assert 30 < s6_readers.step_roofline(ctx) < 100
    # 300 + 2,048 tokens through 26 layers in 20 + 40 ms of the scan
    assert s6_readers.scan_us_per_token(ctx) == pytest.approx(
        1e6 * 0.060 / (2348 * 26))
    monkeypatch.setattr(scopes, "device_share",
                        lambda ctx, scope: {"mamba_norm": 1.25}[scope])
    assert s6_readers.norm_share(ctx) == 1.25
    # nothing to read: a trace that cannot be paired, no device time under
    # the scopes, an untraced run
    monkeypatch.setattr(scopes, "load", lambda path: [])
    assert s6_readers.step_roofline(ctx) is None
    assert s6_readers.scan_us_per_token(ctx) is None
    monkeypatch.setattr(dispatch_readers, "_reduced", lambda ctx: None)
    assert s6_readers.step_roofline(ctx) is None
    assert s6_readers.scan_us_per_token(ctx) is None
    monkeypatch.undo()
    untraced = _Ctx([], traced=False)
    for name in NEW_READERS:
        module = mf.find_module(mf.HERE, "layer_metrics", name)
        assert module.reduce(untraced) is None, name


# --------------------------------------------------------------- controls

def test_each_planted_fault_fails_the_cells_own_check(tiny, checkout):  # noqa: F811
    """``python3 -m benchmark.jamba_controls`` at the twin's size, where
    the check's limits are float32's: the engine as served passes (its
    slots used before); the S6 layers without their inner norms and a
    fresh row that inherits its slot's state (caught by the probe's row)
    each fail, and the registry's entry is as it was afterwards. And
    ``controls.py``'s lost block, on the module's engine: caught by the
    pool's rows."""
    from benchmark import controls, jamba_controls
    from deepspeed_tpu.models.mixers import KINDS, mamba1

    info = mf.resolve(mf.load(checkout), CELL, checkout)
    prompt = np.random.default_rng(8).integers(0, 256, size=PROMPT).tolist()
    seen = {name: (expected, record["ok"], record.get("max_rel_err"))
            for name, expected, record in jamba_controls.run(info, 8, prompt)}
    assert list(seen) == ["served", "no_inner_norm", "stale_slot"]
    assert all(expected == ok for expected, ok, _ in seen.values()), seen
    assert min(seen["no_inner_norm"][2], seen["stale_slot"][2]) > 0.01
    assert KINDS["mamba1"].paged is mamba1.paged
    *_, params, _, _, engine = tiny
    lost = controls.measure(info, "lost_block_g0",
                            controls.LostBlock(engine, 0), params, prompt)
    assert not lost["ok"] and lost["max_rel_err"] > 0.1
    sm = engine.state_manager
    assert sm.allocator.free_blocks == sm.allocator.total_blocks


# ----------------------------------------------------------- the rehearsal

def test_the_cell_rehearsed_on_the_cpu(checkout, capsys):  # noqa: F811
    path = os.path.join(checkout, "benchmark/workloads", CELL + ".json")
    _write(path, dict(_read(path), rate_rps=30.0, trace_s=1.0))
    manifest = mf.load(checkout)
    mf.validate(manifest, checkout)
    info = mf.resolve(manifest, CELL, checkout)
    assert info["config"] == twin()
    assert info["traffic"] == _read(os.path.join(TWINS, "traffic",
                                                 "chatrate.json"))
    line, extra = rehearse(checkout, capsys, CELL, 1)
    assert line["correct"], extra["why_not"]
    assert line["failed"] == 0 and line["attempted"] > 20
    # (a rehearsal whose every program compiled here, none out of the
    # run's shared cache, reads that share as 0: ``check_line`` takes no 0)
    if line["metrics"].get("setup_cache_hit_share", {}).get("value") == 0:
        del line["metrics"]["setup_cache_hit_share"]
    check_line(line, manifest, CELL, "per_layer")
    counters = extra["counters"]
    check = counters["logits_check"]
    assert 0 < check["max_rel_err"] < 1e-4 and check["rms_rel_err"] < 1e-4
    # 4 logits rows, the pool's rows and the probe's a request
    assert check["views"] == check["sampled"] and check["compared"] > 10
    assert counters["state_slots_held"] == 0
    # 7 sequence buckets x 6 chunk buckets, then each count 1..40
    assert counters["warm_up_calls"] == 7 * 6 + 40
    # off the chip the counters and the spans are read, the device is not
    metrics = line["metrics"]
    assert {"batch_seqs_mean", "pad_ratio", "state_slots_peak_share",
            "kv_blocks_peak_share", "queue_wait_p50_ms"} <= set(metrics), \
        sorted(metrics)
    assert metrics["state_slots_peak_share"]["value"] <= 100
    assert not set(NEW_READERS) & set(metrics)
    assert not {"mamba_share", "attn_full_share"} & set(metrics)
