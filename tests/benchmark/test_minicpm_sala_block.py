"""The ``minicpm_sala`` block as the benchmark finds it: the manifest with
its entries, the configuration against the catalog row it was drawn from,
the issue's arithmetic, the reference against the program's model at the
tiny twin's size — ``CausalLM.apply``, and prefill in chunks then decode
through the pool and the state slots on the XLA and the interpreted
Pallas paths — the chunked recurrence against its step folded, the
selected sets against the reference's, a selection switched off and a
dropped multiplier failing, a compressed key that crosses a block's edge,
every block and slot coming back, the typed refusals, the scope names,
the new readers on hand-made contexts, and the cell rehearsed end to end
on the CPU under the real names. The twin selects from position 32 on
(kernel 4 / stride 2 / block 8 / topk 2 / window 16), so a 100-token
prompt is selected over for two thirds of its positions."""

import json
import os

import numpy as np
import pytest
from test_benchmark_runners import (_read, _write, check_line,  # noqa: F401
                                    checkout, rehearse)

from benchmark import manifest as mf
from benchmark import sala_readers, scopes
from benchmark.model import check_consistent

CELL, CONFIG = "minicpm-sala.deepctx", "minicpm-sala"
NEW_READERS = ("lightning_share", "lightning_scan_share",
               "block_select_share", "block_select_ratio",
               "sparse_attn_share", "paged_attn_select_roofline",
               "paged_attn_mask_roofline")
SHARED_READERS = ("gen_late_p99_ms", "queue_wait_p50_ms",
                  "kv_blocks_peak_share", "state_slots_peak_share",
                  "fwd_mixed_dev_ms", "dev_prefill_us_per_token",
                  "prefill_own_share")
CATALOG_FILE = "/opt/skills/guides/model-configs/architectures.jsonl"
TWINS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "twins")
PROMPT, STEPS = 100, 6


def block():
    return mf.find_module(mf.HERE, "blocks", "minicpm_sala")


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
    assert len(manifest["workloads"]) >= 9
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) \
        <= max(1, len(manifest["workloads"]) // 4)
    assert info["block"].__name__.endswith("minicpm_sala")
    assert info["traffic"]["loop"] == "open"
    assert info["traffic"]["generator"] == "stratified"
    assert info["traffic"]["prompt_tokens"] == {
        "median": 24576, "sigma": 0.7, "min": 9216, "max": 98304}
    assert info["traffic"]["output_tokens"] == {
        "median": 384, "sigma": 0.6, "min": 64, "max": 1024}
    assert info["cell"]["chips"] == 1 and info["workload"]["serving"] == {}
    mine = {m["name"] for m in mf.metrics_for(manifest, "per_layer", CELL)}
    assert set(NEW_READERS) | set(SHARED_READERS) <= mine
    # kernels this model's layers do not run, experts and latents it has
    # not
    assert not mine & {"paged_attn_roofline", "paged_attn_hybrid_roofline",
                       "gmm_roofline", "gdn_share", "experts_share",
                       "moe_rows_per_expert", "latent_attn_share",
                       "kv_expand_ratio", "sparse_select_ratio", "mfu"}
    # judged on the time to the first token: over six runs the p90 of
    # twelve requests' TPOT, one request's, spread by 4.8% against the 4%
    # a new cell is admitted under (PERF.md section 2), as openPangu's did
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
    assert at("configs", CONFIG) > at("configs", "dots3-note-prev")
    assert at("workloads", CELL) > at("workloads", "dots3-note-prev.longctx")
    assert at("per_layer", "lightning_share") > at("per_layer",
                                                   "mla_window_roofline")


def test_the_configuration_is_the_catalog_rows_but_for_what_it_reduces():
    _, info = real()
    config, entry = info["config"], info["config_entry"]
    with open(CATALOG_FILE) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "MiniCPM-SALA")
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == ["num_hidden_layers", "mixer_types"]
    assert sorted(config["reduced"]) == sorted(entry["reduced"])
    for key, value in row["config"].items():
        if key not in entry["reduced"]:
            assert config[key] == value, key
    assert config["num_hidden_layers"] == 8
    assert config["mixer_types"] == (["minicpm4"]
                                     + ["lightning-attn"] * 3) * 2
    # the published ratio, 8 : 24, and its opening layer
    published = row["config"]["mixer_types"]
    assert published.count("minicpm4") * 3 \
        == published.count("lightning-attn") == 24
    assert published[0] == config["mixer_types"][0]
    b = info["block"]
    for c in (config, twin()):
        check_consistent(c, b)
        b.check_scalings(c)
    arch = config["transformer_config"]
    assert arch["layer_pattern"] == ["block_sparse"] + ["lightning"] * 3
    assert arch["residual_scale"] == pytest.approx(1.4 / 32 ** 0.5)
    assert arch["logit_scale"] == 1 / 16 and arch["embed_scale"] == 12
    assert arch["vocab_size"] == 73448 and arch["max_seq_len"] == 99328
    assert (arch["block_kernel_size"], arch["block_kernel_stride"],
            arch["block_select_size"], arch["block_topk"],
            arch["block_init_blocks"], arch["block_window"],
            arch["block_dense_len"]) == (32, 16, 64, 64, 1, 2048, 8192)
    assert arch["block_select_size"] == config["engine"]["kv_block_size"]
    for key in ("mup", "lightning", "decay_slopes", "norm_widths",
                "sparse_sizes", "dense_len_rule", "exact_normaliser",
                "selection", "positions_run"):
        assert config["assumed"][key]
    # every checked prompt is selected over
    assert config["check"]["max_prompt_tokens"] > arch["block_dense_len"]
    assert info["traffic"]["prompt_tokens"]["min"] > arch["block_dense_len"]
    # the twin keeps every switch of the published file's architecture
    tw = twin()["transformer_config"]
    assert set(tw) == set(arch)
    assert all(tw[k] == arch[k] for k in arch
               if isinstance(arch[k], (bool, str, list)) and k != "dtype")


def test_the_arithmetic_is_the_issues():
    _, info = real()
    b, arch = info["block"], info["config"]["transformer_config"]
    M = 1e6
    assert b.mixer_matmul_params(arch, "lightning") / M \
        == pytest.approx(83.9, abs=0.05)
    assert b.mixer_matmul_params(arch, "block_sparse") / M \
        == pytest.approx(52.4, abs=0.05)
    assert b.layer_kinds(arch) == {"lightning": 6, "block_sparse": 2}
    assert b.matmul_params(arch) / M == pytest.approx(
        6 * 285.2 + 2 * 253.8 + 300.8, abs=1)
    # what the program's model holds: 2,820.5 M parameters and the norms
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models import hybrid
    from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig

    cfg = TransformerConfig(**dict(arch, dtype=jnp.bfloat16))
    shapes = jax.eval_shape(CausalLM(cfg).init, jax.random.PRNGKey(0))
    total = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert total / M == pytest.approx(2820.5, abs=0.5)
    # the state: 32 x 128 x 128 float32 a layer a sequence = 2 MiB, six
    # layers, 33 slots: 0.39 GiB
    assert b.state_bytes(arch) == 6 * 2 * 2 ** 20
    state = hybrid.state_shapes(cfg, 33)
    assert state == {"lightning": ((6, 33, 32, 128, 128), jnp.float32)}
    assert 33 * b.state_bytes(arch) / 2 ** 30 == pytest.approx(0.39, abs=0.01)
    # the pool: 1,024 B of k and v and 32 B of compressed keys a token a
    # layer; 1,048,576 tokens x 2 layers = 2.06 GiB
    assert b.kv_token_bytes(arch) == 2 * (1024 + 32)
    engine = info["config"]["engine"]
    tokens = engine["kv_blocks"] * engine["kv_block_size"]
    assert tokens == 1048576
    assert tokens * b.kv_token_bytes(arch) / 2 ** 30 \
        == pytest.approx(2.06, abs=0.01)
    assert cfg.kv_layouts(64)[0]["kc"] == (4, 2, 128)
    # a selecting query keeps at most 1 + 64 + 33 blocks; short of
    # dense_len a row's table holds its whole context, 128 blocks
    z = hybrid.block_sizes(cfg)
    assert (z.per, z.ratio, z.table_width) == (4, 2, 128)
    assert z.init + z.topk + z.window // z.block + 1 == 98
    # the select kernel reads 2 K/V heads x (k, v) x 64 x 128 x 2 B a block
    cost = b.paged_select_cost(arch, 1, 98)
    assert cost["bytes"] == 98 * 65536 + 2 * 32 * 128 * 2
    assert cost["flops"] == 4.0 * 32 * 128 * 98 * 64
    assert b.paged_mask_cost(arch, 0, 0, 1)["flops"] == 4.0 * 32 * 128


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
    want, select = block()._logits_one(
        params, np.asarray(tokens, np.int32), arch, 16)
    return arch, model, params, tokens, np.asarray(want), np.asarray(select)


def test_reference_agrees_with_the_programs_model(tiny):
    import jax
    import jax.numpy as jnp

    arch, model, params, tokens, want, select = tiny
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(model.apply)(
            params, jnp.asarray(tokens)[None]))[0]
    assert np.abs(got - want).max() < 2e-6 * (want.max() - want.min())
    # a query short of dense_len attends everything; from the first
    # position with a candidate more than topk on, a margin is finite
    assert np.isinf(select[:32]).all() and np.isfinite(select[56:]).all()
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
def paths(tiny):
    """The prompt served once on the kernels' XLA formulations and once
    on their Pallas bodies, interpreted, with what each engine counted."""
    from deepspeed_tpu.ops import paged_attention as pa

    arch, model, params, tokens, *_ = tiny
    out = {}
    for name in ("xla", "pallas"):
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(pa, "_FORCE_INTERPRET", name == "pallas")
            engine = _engine(model, params)
            sm = engine.state_manager
            shapes = {k: v.shape for k, v in sm.forward_cache.items()}
            logits = _served(engine, tokens)
            last = dict(engine.last_put)
            occupancy = sm.occupancy()
            engine.flush(7)
            out[name] = dict(
                logits=logits, totals=dict(engine.put_totals), shapes=shapes,
                last=last, occupancy=occupancy,
                free=(sm.allocator.free_blocks, sm.free_state_slots,
                      len(sm._free_id_slots)),
                total=(sm.allocator.total_blocks, sm.state_slots,
                       sm.id_slots))
    return out


@pytest.mark.parametrize("name", ["xla", "pallas"])
def test_chunks_then_decode_through_the_pool_and_the_slots(tiny, paths, name):
    arch, *_, want, _ = tiny
    run = paths[name]
    # one group: k and v by K/V head, four compressed keys a block beside
    # them; six lightning layers' state, a slot a sequence and a scratch
    assert run["shapes"] == {"k": (2, 128, 2, 8, 16), "v": (2, 128, 2, 8, 16),
                             "kc": (2, 128, 4, 2, 16),
                             "lightning": (6, 5, 4, 16, 16)}
    assert _worst(run["logits"], want) < 2e-6
    totals = run["totals"]
    n = PROMPT + STEPS
    assert totals["lightning_rows"] == totals["tokens_valid"] == n
    assert "moe_rows_routed" not in totals
    # positions 0-31 attend every block of their past; a later one its
    # first block, two more (one while only one lies before its window)
    # and the two or three its window of 16 reaches
    t = np.arange(n)
    live = t // 8 + 1
    first_w = np.maximum(t - 15, 0) // 8
    picked = np.where(t < 32, live,
                      1 + np.clip(first_w - 1, 0, 2) + t // 8 - first_w + 1)
    assert totals["sparse_rows_dense"] == 32
    assert totals["sparse_rows_selected"] == n - 32
    assert totals["sparse_blocks_live"] == live.sum()
    assert totals["sparse_blocks_selected"] == picked.sum()
    assert run["last"]["sparse_blocks_ones"] == picked[-1] == 6
    assert run["last"]["sparse_blocks_live"] == 14
    # bytes by leaf: a compressed key a stride of two keys, half of k
    # (a sixteenth at the published stride)
    leaves = run["occupancy"]["leaf_bytes"]
    assert leaves["k"] == leaves["v"] == 2 * leaves["kc"]
    assert leaves["lightning"] == 6 * 5 * 4 * 16 * 16 * 4
    assert run["occupancy"]["bytes_total"] == sum(
        leaves[name] for name in ("k", "v", "kc"))


def test_the_two_paths_and_another_chunking_agree(tiny, paths):
    arch, model, params, tokens, want, _ = tiny
    span = want.max() - want.min()
    assert np.abs(paths["xla"]["logits"] - paths["pallas"]["logits"]
                  ).max() < 2e-6 * span
    # chunks of 24: other kernels end in other forwards, the selection
    # is a position's
    other = _served(_engine(model, params), tokens, chunk=24)
    assert np.abs(other - paths["xla"]["logits"]).max() < 2e-6 * span


@pytest.mark.parametrize("name", ["xla", "pallas"])
def test_every_block_and_slot_comes_back(paths, name):
    run = paths[name]
    assert run["free"] == run["total"]
    assert run["occupancy"]["state_slots_used"] == 1


def test_the_chunked_recurrence_is_the_step_folded():
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops import lightning_attention as la

    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    N, C, H, D = 2, 150, 4, 16
    q, k, v = (jax.random.normal(key, (N, C, H, D)) for key in ks[:3])
    state = jax.random.normal(ks[3], (N, H, D, D))
    slope = la.slopes(H)
    # λ_0 = exp(-2^(-8/32)) = 0.43 at the published 32 heads
    assert float(jnp.exp(-la.slopes(32)[0])) == pytest.approx(0.4313,
                                                              abs=1e-3)
    n = jnp.asarray([C, 70])        # the second row ends inside a tile

    def fold(carry, xs):
        o, new = la.lightning_step(*xs[:3], slope, carry)
        keep = (xs[3] < n)[:, None, None, None]
        return jnp.where(keep, new, carry), o

    want_state, want = jax.lax.scan(
        fold, state, (q.swapaxes(0, 1), k.swapaxes(0, 1), v.swapaxes(0, 1),
                      jnp.arange(C)))
    chunked = jax.jit(la.lightning_chunked)
    got, got_state = chunked(q, k, v, slope, state, n)
    want = np.asarray(want.swapaxes(0, 1))
    close = lambda a, b: np.abs(np.asarray(a) - np.asarray(b)  # noqa: E731
                                ).max() < 5e-6 * np.abs(np.asarray(b)).max()
    assert close(got[0], want[0]) and close(got[1, :70], want[1, :70])
    assert close(got_state, want_state)
    # a row with no valid token hands its state back bit for bit
    _, same = chunked(q, k, v, slope, state, jnp.asarray([0, 0]))
    assert (np.asarray(same) == np.asarray(state)).all()
    # the fastest head's decay over a tile underflows nothing: λ^64 of
    # head 0 at 32 heads is exp(-54), and no power is negative
    assert np.isfinite(np.asarray(chunked(
        q, k, v, la.slopes(32)[:H], state, n)[0])).all()


def test_the_selected_sets_are_the_references_wherever_the_edge_is_clear(
        tiny):
    """The first layer's selection on the embedding: the program's
    functions (``full_qkv``, the compressed keys, the block scores, the
    selection as a mask and as a one-token row's table) against the
    reference's sets, at every position whose margin is above
    ``SELECT_EPS``."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models import hybrid

    arch, model, params, tokens, *_ = tiny
    b, cfg = block(), model.cfg
    toks = jnp.asarray(tokens, jnp.int32)
    T = len(tokens)
    want, margin = map(np.asarray, b.selected_blocks(params, toks, arch,
                                                     q_block=16))
    clear = margin > b.SELECT_EPS
    assert clear.sum() > 0.8 * T and (~np.isinf(margin)).sum() > 0.5 * T
    z = hybrid.block_sizes(cfg)
    blocks = -(-T // z.block)

    @jax.jit
    def program(params):
        lp = jax.tree.map(lambda a: a[0], params["layers"]["slot0"])
        h1 = hybrid.block_norm(
            cfg, params["embed"]["wte"][toks][None] * cfg.embed_scale,
            lp["attn_norm_w"])
        q, k, *_ = hybrid.full_qkv(cfg, h1, lp, lambda t: t)
        kc = hybrid.block_compress(z, jnp.pad(k, (
            (0, 0), (0, z.stride * (blocks * z.per + z.ratio - 1) - T),
            (0, 0), (0, 0))))
        at = jnp.arange(T)[None]
        scores = hybrid.block_scores(cfg, q, kc, at)
        return (hybrid.block_keep(cfg, scores, at)[0],
                hybrid.block_select(cfg, scores[0], at[0]))

    with jax.default_matmul_precision("highest"):
        mask, (table, n) = jax.tree.map(np.asarray, program(params))
    assert mask.shape == want.shape == (T, 2, blocks)
    assert (mask[clear] == want[clear]).all()
    # the two forms of one selection are the same set, its blocks in
    # order and the query's own last
    for t in range(T):
        for h in range(2):
            row = table[t, h, :n[t]]
            assert (np.diff(row) > 0).all() and row[-1] == t // z.block
            assert set(row) == set(np.flatnonzero(mask[t, h]))
    assert (n == mask[:, 0].sum(-1)).all()


@pytest.mark.parametrize("change", [
    {"block_topk": 64}, {"block_window": 8, "block_dense_len": 32},
    {"residual_scale": 1.0}, {"logit_scale": 1.0}, {"embed_scale": 1.0}],
    ids=lambda c: next(iter(c)))
def test_a_changed_selection_or_a_dropped_multiplier_fails(tiny, change):
    """The same weights served with the selection switched off (a top-k
    as long as the table: a chunk row attends unselected keys), with
    another window, or without one of the three multipliers, against the
    reference: the comparison that passes at 2e-6 fails by orders (the
    selection's by two: the twin attends nearly evenly, and what a block
    more or less moves is small)."""
    import jax.numpy as jnp

    from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig

    import jax

    arch, _, params, tokens, want, _ = tiny
    other = CausalLM(TransformerConfig(**dict(arch, dtype=jnp.float32,
                                              **change)))
    if "block_topk" in change or "logit_scale" in change:
        # through the pool: a chunk row that attends unselected keys, and
        # the multiplier the paged forward applies itself
        got = _served(_engine(other, params), tokens)
    else:
        got = np.asarray(jax.jit(other.apply)(
            params, jnp.asarray(tokens)[None]))[0, PROMPT - 1:]
    assert _worst(got, want) > (1e-4 if "block_topk" in change
                                or "block_window" in change else 1e-3)


def test_a_compressed_key_that_crosses_a_blocks_edge_is_written_once_and_right(
        tiny):
    """``_block_compress`` over a pool whose table is out of order, fed
    5 positions a forward and then one: after every forward the ``kc``
    leaf holds exactly the kernels that have ended — kernel 4b + 3 in
    block b's last row only once two keys of block b + 1 are in — and
    each is the mean of its four keys."""
    import jax
    import jax.numpy as jnp

    arch, model, params, *_ = tiny
    compress = jax.jit(_engine(model, params).paged._block_compress,
                       static_argnums=(2, 6))
    T, bs, KH, D = 44, 8, 2, 16
    keys = np.random.default_rng(0).normal(size=(T, KH, D)).astype(np.float32)
    table = np.asarray([[9, 3, 12, 5, 7, 2]], np.int32)
    k_pool = np.zeros((1, 16, KH, bs, D), np.float32)
    for t in range(T):
        k_pool[0, table[0, t // bs], :, t % bs] = keys[t]
    kc = jnp.zeros((1, 16, 4, KH, D), jnp.float32)
    want = np.zeros((1, 16, 4, KH, D), np.float32)
    at = 0
    for n in [5] * 6 + [1] * 14:
        visible = np.array(k_pool)
        for t in range(at + n, T):      # what a later forward brings
            visible[0, table[0, t // bs], :, t % bs] = 0
        kc = compress(
            jnp.asarray(visible), kc, 0, jnp.asarray(table),
            jnp.asarray([at]), jnp.asarray([n]), 8 if n > 1 else 1)
        at += n
        for j in range(0, (at - 4) // 2 + 1):
            want[0, table[0, j // 4], j % 4] = keys[2 * j:2 * j + 4].mean(0)
        assert np.abs(np.asarray(kc) - want).max() < 1e-6, at
    assert at == T
    # kernel 3 (keys 6-9) sits in block 0's last row; block 5 has kernels
    # 20 (keys 40-43) and no other
    assert np.abs(want[0, 9, 3]).sum() > 0 and np.abs(want[0, 2, 0]).sum() > 0
    assert np.abs(want[0, 2, 1:]).sum() == 0


def test_each_refused_feature_raises_its_own_error_and_the_rest_works(tiny):
    import jax.numpy as jnp

    from deepspeed_tpu.models.hybrid import (CompressedKeysUnsupported,
                                             RecurrentStateUnsupported)
    from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig

    arch, model, params, tokens, *_ = tiny
    # a recurrent state: what it is refused today stays refused
    with pytest.raises(RecurrentStateUnsupported, match="prefix cache"):
        _engine(model, params, enable_prefix_cache=True)
    engine = _engine(model, params)
    engine.put([1], [tokens[:32]])
    with pytest.raises(RecurrentStateUnsupported, match="trim_sequence"):
        engine.trim_sequence(1, 2)
    with pytest.raises(RecurrentStateUnsupported, match="prefix cache"):
        engine.configure_prefix_cache(True)
    with pytest.raises(RecurrentStateUnsupported, match="KV tier"):
        engine.configure_kv_tier(True)
    with pytest.raises(RecurrentStateUnsupported, match="verif"):
        engine.put([2], [tokens[:8]], verify_width=4)
    # compressed keys beside k / v: a scale a head knows no such leaf
    with pytest.raises(CompressedKeysUnsupported, match="quantized"):
        _engine(model, params, kv_quant_enabled=True)
    # ... and without any recurrent layer a block is still its own
    # sequence's: the last row of a block is a kernel its successor ends
    sparse_only = CausalLM(TransformerConfig(**dict(
        arch, dtype=jnp.float32, num_layers=2,
        layer_pattern=["block_sparse"], rope_kinds=[])))
    with pytest.raises(CompressedKeysUnsupported, match="prefix cache"):
        _engine(sparse_only, sparse_only.init(__import__("jax").random.PRNGKey(
            0)), enable_prefix_cache=True)
    # a pool block that is not the selection's block
    with pytest.raises(ValueError, match="block_select_size"):
        _engine(model, params, kv_block_size=16)
    # and beside them the engine serves: the sequence goes on, another
    # starts, both give every block and slot back
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
        body + "lightning_attn/lightning_proj/dot_general:": "lightning_proj",
        body + "lightning_attn/lightning_scan/while/body/closed_call/"
               "dot_general:": "lightning_scan",
        body + "lightning_attn/lightning_out/mul:": "lightning_out",
        body + "lightning_attn/scatter:": "lightning_attn",
        body + "sparse_attn/block_compress/gather:": "block_compress",
        body + "sparse_attn/while/body/closed_call/block_score/exp:":
            "block_score",
        body + "sparse_attn/while/body/closed_call/block_select/while/"
               "body/reduce_sum:": "block_select",
        body + "sparse_attn/attend/paged_attention_select/pallas_call:":
            "attend",
        body + "sparse_attn/attend/paged_attention_mask/pallas_call:":
            "attend",
        body + "sparse_attn/qkv/dot_general:": "qkv",
        body + "sparse_attn/kv_write/scatter:": "kv_write",
        body + "sparse_attn/mul:": "sparse_attn",
        body + "mlp/dense_mlp/dot_general:": "dense_mlp",
    }
    for op_name, want in cases.items():
        assert scopes.scope_of(op_name, b.SCOPES) == want, op_name
    assert "sparse_attn" in scopes.scope_path(
        body + "sparse_attn/attend/paged_attention_select/pallas_call:",
        b.SCOPES)
    assert set(b.LIGHTNING_SCOPES) | set(b.SELECT_SCOPES) < set(b.SCOPES)


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
        jnp.zeros((1, 32), jnp.int32), jnp.zeros((1,), jnp.int32)
    ).compile().as_text()
    for name in block().SCOPES:
        assert f"/{name}/" in text or f"/{name}\"" in text, name


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
    base = {"valid_tokens": 0, "sparse_rows_dense": 0,
            "sparse_rows_selected": 0, "sparse_blocks_live": 0,
            "sparse_blocks_selected": 0, "sparse_ones": 0,
            "sparse_blocks_ones": 0, "sparse_q_chunk": 0,
            "sparse_pairs_chunk": 0, "sparse_keys_chunk": 0,
            "lightning_rows": 0}
    return dict(base, **over)


def test_the_new_readers_on_hand_made_contexts():
    from benchmark import peaks

    b = block()
    arch = real()[1]["config"]["transformer_config"]
    chunk = _record(valid_tokens=2048, sparse_rows_selected=2048,
                    sparse_blocks_live=2048 * 400,
                    sparse_blocks_selected=2048 * 98, sparse_q_chunk=2048,
                    sparse_pairs_chunk=2048 * 6240,
                    sparse_keys_chunk=64 + 2048 + 2047)
    step = _record(valid_tokens=4, sparse_rows_selected=4,
                   sparse_blocks_live=4 * 500, sparse_blocks_selected=4 * 98,
                   sparse_ones=4, sparse_blocks_ones=4 * 98)
    last = _record(valid_tokens=1, sparse_ones=1, sparse_blocks_ones=98)
    ctx = _Ctx([chunk, step, last], {
        "kernel:paged_attention_select": 0.0001,
        "kernel:paged_attention_mask": 0.02})
    assert sala_readers.select_ratio(ctx) == pytest.approx(
        (2048 * 98 + 4 * 98) / (2048 * 400 + 4 * 500))
    least = lambda cost: peaks.roofline_seconds(cost, "TPU v5 lite")  # noqa
    # the last forward may still run when the profiler stops: left out
    assert sala_readers.select_roofline(ctx) == pytest.approx(
        100 * 2 * least(b.paged_select_cost(arch, 4, 4 * 98)) / 0.0001)
    assert sala_readers.mask_roofline(ctx) == pytest.approx(
        100 * 2 * least(b.paged_mask_cost(arch, 2048, 64 + 2048 + 2047,
                                          2048 * 6240)) / 0.02)
    assert 0 < sala_readers.select_roofline(ctx) < 100
    assert 0 < sala_readers.mask_roofline(ctx) < 100
    # nothing to read: an untraced run, the parent's spans, no kernel
    assert sala_readers.select_roofline(
        _Ctx([chunk, step], traced=False)) is None
    assert sala_readers.attend_share(_Ctx([chunk], traced=False)) is None
    assert sala_readers.lightning_share(_Ctx([chunk], traced=False)) is None
    parent = {k: v for k, v in chunk.items()
              if not k.startswith(("sparse_", "lightning_"))}
    assert sala_readers.select_ratio(_Ctx([parent, parent])) is None
    assert sala_readers.mask_roofline(_Ctx([parent, parent, parent], {
        "kernel:paged_attention_mask": 0.001})) is None
    assert sala_readers.select_roofline(_Ctx([chunk, step, last])) is None


# ----------------------------------------------------------- the rehearsal

@pytest.mark.parametrize("traced", [1])
def test_the_cell_rehearsed_on_the_cpu(checkout, capsys, traced):  # noqa: F811
    """The whole runner over the engine at the tiny twin's size, under
    the real names (the twin and its mix reach the checkout through
    ``tests/conftest.py``, found by name): prompts in several chunks
    beside decoding rows, the pool and the state slots, the logits check
    against this block's reference, every block back. Traced only: the
    untraced line is the harness's own, held by the other cells'
    rehearsals."""
    path = os.path.join(checkout, "benchmark/workloads", CELL + ".json")
    _write(path, dict(_read(path), rate_rps=10.0, trace_s=1.0))
    manifest = mf.load(checkout)
    mf.validate(manifest, checkout)
    info = mf.resolve(manifest, CELL, checkout)
    assert info["config"] == twin()
    assert info["traffic"] == _read(os.path.join(TWINS, "traffic",
                                                 "deepctx.json"))
    line, extra = rehearse(checkout, capsys, CELL, traced)
    assert line["correct"], extra["why_not"]
    assert line["failed"] == 0 and line["attempted"] > 5
    check_line(line, manifest, CELL, "per_layer")
    check = extra["counters"]["logits_check"]
    assert 0 < check["max_rel_err"] < 1e-4 and check["rms_rel_err"] < 1e-4
    # off the chip the counters are read, the device is not
    assert {"block_select_ratio", "state_slots_peak_share",
            "kv_blocks_peak_share"} <= set(line["metrics"])
    # prompts of 40-200 under a selection of six blocks at most
    assert 0.3 < line["metrics"]["block_select_ratio"]["value"] < 0.9
    assert not {"lightning_share", "sparse_attn_share", "block_select_share",
                "paged_attn_select_roofline", "paged_attn_mask_roofline"} \
        & set(line["metrics"])
