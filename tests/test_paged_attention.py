"""Pallas paged attention: kernel (interpret mode) vs XLA gather reference.

Mirrors the reference's ragged-ops kernel tests
(tests/unit/inference/kernels/ragged_ops/test_blocked_flash.py pattern:
build a paged cache + block tables, compare against a dense reference)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import paged_attention as pa


def _build_case(rng, N, C, H, KH, D, bs, MB, NB, ctx_lens):
    """Random pool + per-seq disjoint block tables with given context."""
    q = jnp.asarray(rng.standard_normal((N, C, H, D)), jnp.float32)
    k_pool = jnp.asarray(rng.standard_normal((NB, KH, bs, D)), jnp.float32)
    v_pool = jnp.asarray(rng.standard_normal((NB, KH, bs, D)), jnp.float32)
    # assign disjoint blocks per sequence
    perm = rng.permutation(NB)
    tables = np.full((N, MB), -1, np.int64)
    pos = 0
    start_pos, n_tokens = [], []
    for i, ctx in enumerate(ctx_lens):
        nblk = -(-ctx // bs)
        assert nblk <= MB and pos + nblk <= NB
        tables[i, :nblk] = perm[pos:pos + nblk]
        pos += nblk
        n_tok = min(C, ctx)           # last n_tok positions are "this chunk"
        start_pos.append(ctx - n_tok)
        n_tokens.append(n_tok)
    return (q, k_pool, v_pool, jnp.asarray(tables, jnp.int32),
            jnp.asarray(start_pos, jnp.int32), jnp.asarray(n_tokens, jnp.int32))


CASES = [
    # N, C, H, KH, D, bs, MB, NB, ctx_lens
    (3, 1, 4, 4, 64, 16, 4, 16, [1, 17, 50]),        # pure decode, MHA
    (3, 1, 8, 2, 64, 16, 4, 16, [5, 33, 64]),        # pure decode, GQA
    (2, 8, 4, 2, 64, 16, 6, 16, [8, 40]),            # prefill chunks, GQA
    (4, 4, 4, 1, 128, 8, 8, 32, [4, 7, 30, 64]),     # MQA, ragged mix
]


@pytest.mark.parametrize("case", CASES)
def test_pallas_matches_xla(case, monkeypatch):
    monkeypatch.setattr(pa, "_FORCE_INTERPRET", True)
    N, C, H, KH, D, bs, MB, NB, ctx_lens = case
    rng = np.random.default_rng(0)
    q, kp, vp, tbl, sp, nt = _build_case(rng, N, C, H, KH, D, bs, MB, NB,
                                         ctx_lens)
    ref = pa.paged_attention_xla(q, kp, vp, tbl, sp, nt)
    out = pa.paged_attention(q, kp, vp, tbl, sp, nt)
    # compare only valid rows (dead rows are unspecified)
    for i in range(N):
        v = int(nt[i])
        np.testing.assert_allclose(np.asarray(out)[i, :v],
                                   np.asarray(ref)[i, :v],
                                   atol=2e-5, rtol=2e-5)


def test_decode_matches_full_attention(monkeypatch):
    """Paged decode of one new token == dense causal attention at that row."""
    monkeypatch.setattr(pa, "_FORCE_INTERPRET", True)
    rng = np.random.default_rng(1)
    H, KH, D, bs = 4, 2, 64, 8
    ctx = 21                                          # 20 cached + 1 new
    q1 = jnp.asarray(rng.standard_normal((1, 1, H, D)), jnp.float32)
    # a dense context [S, KH, D], then page it into a shuffled pool
    k_ctx = rng.standard_normal((ctx, KH, D)).astype(np.float32)
    v_ctx = rng.standard_normal((ctx, KH, D)).astype(np.float32)
    MB = -(-ctx // bs)
    NB = MB + 3
    pool_ids = rng.permutation(NB)[:MB]
    k_pool = np.zeros((NB, KH, bs, D), np.float32)
    v_pool = np.zeros((NB, KH, bs, D), np.float32)
    for b in range(MB):
        lo, hi = b * bs, min((b + 1) * bs, ctx)
        k_pool[pool_ids[b], :, :hi - lo] = k_ctx[lo:hi].transpose(1, 0, 2)
        v_pool[pool_ids[b], :, :hi - lo] = v_ctx[lo:hi].transpose(1, 0, 2)
    tables = np.full((1, MB), -1, np.int64)
    tables[0, :MB] = pool_ids
    out = pa.paged_attention(q1, jnp.asarray(k_pool), jnp.asarray(v_pool),
                             jnp.asarray(tables, jnp.int32),
                             jnp.asarray([ctx - 1], jnp.int32),
                             jnp.asarray([1], jnp.int32))
    # dense reference over the unshuffled context
    from deepspeed_tpu.models.transformer import attention_reference

    ref = attention_reference(q1, jnp.asarray(k_ctx)[None],
                              jnp.asarray(v_ctx)[None], causal=True)
    np.testing.assert_allclose(np.asarray(out)[0, 0], np.asarray(ref)[0, 0],
                               atol=2e-5, rtol=2e-5)


def test_padded_rows_never_write_pool():
    """Regression: padded tokens (n_tokens < C) must not scatter K/V into
    the pool — a -1 write sentinel would wrap to pool block NB-1 (JAX
    normalizes negative scatter indices before the bounds check)."""
    from deepspeed_tpu.inference.v2.paged_model import PagedCausalLM
    from deepspeed_tpu.models.transformer import CausalLM, TINY_TEST

    model = CausalLM(TINY_TEST)
    params = model.init(jax.random.PRNGKey(0))
    bs, NB, MB = 4, 8, 4
    paged = PagedCausalLM(model, bs, MB)
    L = TINY_TEST.num_layers
    kv = {"k": jnp.zeros((L, NB, TINY_TEST.kv_heads, bs, TINY_TEST.head_dim)),
          "v": jnp.zeros((L, NB, TINY_TEST.kv_heads, bs, TINY_TEST.head_dim))}
    # one seq using block 0 only, chunk padded C=8 with n_tokens=3;
    # block NB-1 belongs to nobody and must stay zero
    tokens = jnp.zeros((1, 8), jnp.int32)
    tables = jnp.asarray([[0, -1, -1, -1]], jnp.int32)
    _, new_kv = paged.forward(params, kv, tokens,
                              jnp.asarray([0], jnp.int32),
                              jnp.asarray([3], jnp.int32), tables)
    assert float(jnp.abs(new_kv["k"][:, NB - 1]).max()) == 0.0
    assert float(jnp.abs(new_kv["v"][:, NB - 1]).max()) == 0.0
    # ...and the real tokens did land in block 0
    assert float(jnp.abs(new_kv["k"][:, 0, :, :3]).max()) > 0.0


def test_dead_blocks_no_contribution(monkeypatch):
    """Garbage in unallocated/dead blocks never leaks into the output."""
    monkeypatch.setattr(pa, "_FORCE_INTERPRET", True)
    rng = np.random.default_rng(2)
    q, kp, vp, tbl, sp, nt = _build_case(rng, 2, 1, 4, 2, 64, 16, 4, 16,
                                         [10, 20])
    out1 = pa.paged_attention(q, kp, vp, tbl, sp, nt)
    # poison every pool block not referenced by a live table entry
    live = set()
    tbl_np = np.asarray(tbl)
    for i in range(2):
        nblk = -(-int(sp[i] + nt[i]) // 16)
        live.update(tbl_np[i, :nblk].tolist())
    kp2, vp2 = np.asarray(kp).copy(), np.asarray(vp).copy()
    for b in range(kp2.shape[0]):
        if b not in live:
            kp2[b] = 1e6
            vp2[b] = 1e6
    # also poison dead slots inside the last live block
    for i in range(2):
        ctx = int(sp[i] + nt[i])
        last_b = tbl_np[i, (ctx - 1) // 16]
        kp2[last_b, :, ctx % 16 or 16:] = 1e6
        vp2[last_b, :, ctx % 16 or 16:] = 1e6
    out2 = pa.paged_attention(q, jnp.asarray(kp2), jnp.asarray(vp2),
                              tbl, sp, nt)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("case", [CASES[0], CASES[2]])
def test_alibi_pallas_matches_xla(case, monkeypatch):
    """ALiBi slopes in-kernel == XLA gather reference with the same bias."""
    monkeypatch.setattr(pa, "_FORCE_INTERPRET", True)
    rng = np.random.default_rng(7)
    N, C, H, KH, D, bs, MB, NB, ctx = case
    q, kp, vp, tbl, sp, nt = _build_case(rng, N, C, H, KH, D, bs, MB, NB,
                                         ctx)
    from deepspeed_tpu.models.transformer import alibi_slopes

    slopes = alibi_slopes(H)
    out_k = pa._paged_pallas(q, kp, vp, tbl, sp, nt, alibi_slopes=slopes,
                             interpret=True)
    out_x = pa.paged_attention_xla(q, kp, vp, tbl, sp, nt,
                                   alibi_slopes=slopes)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_x),
                               atol=2e-5, rtol=2e-5)
    # and the bias genuinely changes the result
    out_nobias = pa.paged_attention_xla(q, kp, vp, tbl, sp, nt)
    assert not np.allclose(np.asarray(out_x), np.asarray(out_nobias))


def test_v2_put_matches_dense_alibi(monkeypatch):
    """BLOOM-style (ALiBi + embedding LN) model through the v2 ragged
    engine: put() logits == dense forward at the last position."""
    import dataclasses

    from deepspeed_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2, RaggedInferenceEngineConfig)
    from deepspeed_tpu.models.transformer import CausalLM, TINY_TEST

    cfg = dataclasses.replace(
        TINY_TEST, num_kv_heads=4, position="alibi", norm="layernorm",
        activation="gelu", use_bias=True, embedding_layernorm=True)
    model = CausalLM(cfg)
    vcfg = RaggedInferenceEngineConfig(
        max_ragged_batch_size=128, max_ragged_sequence_count=4,
        max_chunk_tokens=32, kv_blocks=32, kv_block_size=8,
        max_tracked_sequences=8)
    engine = InferenceEngineV2(model, config=vcfg)
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.vocab_size, size=20).tolist()
    logits = engine.put([1], [prompt])
    # the engine's tree is in the serving layout (``wqkv``); the model
    # reads three leaves
    from deepspeed_tpu.inference.v2.paged_model import split_qkv

    full = model.apply(split_qkv(cfg, engine.params),
                       jnp.asarray([prompt], jnp.int32))
    np.testing.assert_allclose(np.asarray(logits)[0],
                               np.asarray(full)[0, -1], atol=2e-3,
                               rtol=2e-3)


@pytest.mark.parametrize("window", [8, 20, 48])
def test_sliding_window_pallas_matches_xla(window, monkeypatch):
    """Windowed paged kernel (Mistral serving) vs the XLA gather reference
    with the same window clamp."""
    monkeypatch.setattr(pa, "_FORCE_INTERPRET", True)
    N, C, H, KH, D, bs, MB, NB = 3, 4, 4, 2, 64, 16, 4, 16
    rng = np.random.default_rng(1)
    q, kp, vp, tbl, sp, nt = _build_case(rng, N, C, H, KH, D, bs, MB, NB,
                                         [4, 37, 64])
    ref = pa.paged_attention_xla(q, kp, vp, tbl, sp, nt, window=window)
    out = pa.paged_attention(q, kp, vp, tbl, sp, nt, window=window)
    for i in range(N):
        v = int(nt[i])
        np.testing.assert_allclose(np.asarray(out)[i, :v],
                                   np.asarray(ref)[i, :v],
                                   atol=2e-5, rtol=2e-5)


def test_sliding_window_drops_old_context(monkeypatch):
    """A decode step whose window excludes the early context must ignore it:
    perturbing pre-window K/V slots must not change the output."""
    monkeypatch.setattr(pa, "_FORCE_INTERPRET", True)
    N, C, H, KH, D, bs, MB, NB = 1, 1, 2, 2, 64, 8, 8, 16
    window = 16
    rng = np.random.default_rng(2)
    ctx = 60                               # decode at position 59
    q, kp, vp, tbl, sp, nt = _build_case(rng, N, C, H, KH, D, bs, MB, NB,
                                         [ctx])
    out = pa.paged_attention(q, kp, vp, tbl, sp, nt, window=window)
    # positions attended: (59 − 16, 59] = [44, 59] → pool blocks holding
    # positions < 40 are entirely outside the window; scramble them
    kp2, vp2 = np.asarray(kp).copy(), np.asarray(vp).copy()
    dead_blocks = np.asarray(tbl)[0, :5]   # positions 0..39
    kp2[dead_blocks] = rng.standard_normal(kp2[dead_blocks].shape)
    vp2[dead_blocks] = rng.standard_normal(vp2[dead_blocks].shape)
    out2 = pa.paged_attention(q, jnp.asarray(kp2), jnp.asarray(vp2), tbl,
                              sp, nt, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out2),
                               atol=1e-6, rtol=1e-6)


# ------------------------------------------------- the walk over live blocks

def _walk_case(rng, N, C, H, KH, D, bs, MB, NB, ctx_lens, n_tokens=None,
               dtype=jnp.float32, quant=None):
    """As ``_build_case`` with the rows' valid tokens given (0 = a padded
    row of the bucket: no context, no table) and the pool's dtype: a float
    one, or ``quant`` (int8 / fp8) with its [NB, KH] scale planes."""
    n_tokens = [min(C, c) for c in ctx_lens] if n_tokens is None else n_tokens
    q = jnp.asarray(rng.standard_normal((N, C, H, D)), dtype)
    scales = {}
    if quant is None:
        kp, vp = (jnp.asarray(rng.standard_normal((NB, KH, bs, D)), dtype)
                  for _ in range(2))
    else:
        kp, vp = (jnp.asarray(rng.integers(-120, 121, (NB, KH, bs, D)),
                              jnp.float32).astype(quant) for _ in range(2))
        scales = {f"{n}_scale": jnp.asarray(
            rng.uniform(0.005, 0.02, (NB, KH)), jnp.float32) for n in "kv"}
    # block 0 belongs to nobody: it is where a slot of −1 would land
    perm = 1 + rng.permutation(NB - 1)
    tables = np.full((N, MB), -1, np.int64)
    pos = 0
    for i, ctx in enumerate(ctx_lens):
        nblk = -(-ctx // bs)
        tables[i, :nblk] = perm[pos:pos + nblk]
        pos += nblk
    sp = [c - n for c, n in zip(ctx_lens, n_tokens)]
    return ((q, kp, vp, jnp.asarray(tables, jnp.int32),
             jnp.asarray(sp, jnp.int32), jnp.asarray(n_tokens, jnp.int32)),
            scales)


# 64-token blocks in a table of 20: ``_tiles`` folds 8 blocks a turn, so the
# contexts are 0 live blocks (a padded row), 1, 8 (one whole turn), 11 (not
# a multiple) and 20 (the table's width).
_CTX = [0, 30, 512, 700, 1280]
_GEOM = dict(N=5, bs=64, MB=20, NB=48, ctx_lens=_CTX)
WALK_CASES = {
    # all heads a step: MHA and GQA decode, one token a row
    "mha_decode": dict(C=1, H=4, KH=4, D=64, n_tokens=[0, 1, 1, 1, 1]),
    "gqa_32_8_decode": dict(C=1, H=32, KH=8, D=64, n_tokens=[0, 1, 1, 1, 1]),
    "gqa_16_2_d256_decode": dict(C=1, H=16, KH=2, D=256,
                                 n_tokens=[0, 1, 1, 1, 1]),
    # chunk rows, some short of C (their tail rows are unspecified)
    "gqa_chunk": dict(C=8, H=8, KH=2, D=64, n_tokens=[0, 8, 3, 8, 5]),
    # G·C over MAX_QUERY_ROWS (patched to 16): cut along C into 3 pieces,
    # a piece past a row's tokens walks nothing
    "query_rows_cut": dict(C=24, H=4, KH=2, D=64, n_tokens=[0, 24, 5, 9, 17],
                           max_rows=16),
    "window_200": dict(C=4, H=4, KH=2, D=64, n_tokens=[0, 4, 4, 4, 4],
                       kw=dict(window=200)),
    "window_700_decode": dict(C=1, H=4, KH=4, D=64, n_tokens=[0, 1, 1, 1, 1],
                              kw=dict(window=700)),
    "alibi_mha": dict(C=1, H=4, KH=4, D=64, n_tokens=[0, 1, 1, 1, 1],
                      alibi=True),
    "alibi_gqa_chunk": dict(C=4, H=8, KH=2, D=64, n_tokens=[0, 4, 2, 4, 4],
                            alibi=True),
    "int8_pool": dict(C=1, H=8, KH=2, D=64, n_tokens=[0, 1, 1, 1, 1],
                      quant=jnp.int8, atol=2e-4),
    "int8_pool_chunk": dict(C=4, H=4, KH=4, D=64, n_tokens=[0, 4, 4, 2, 4],
                            quant=jnp.int8, atol=2e-4),
    "fp8_pool": dict(C=1, H=8, KH=2, D=64, n_tokens=[0, 1, 1, 1, 1],
                     quant=jnp.float8_e4m3fn, atol=2e-4),
    # bf16 as served, against the float32 formulation of the same values:
    # what is left is p's and the output's rounding to bf16
    "bf16_pool_decode": dict(C=1, H=8, KH=2, D=128, dtype=jnp.bfloat16,
                             n_tokens=[0, 1, 1, 1, 1], atol=1e-2),
    "bf16_pool_chunk": dict(C=8, H=4, KH=4, D=128, dtype=jnp.bfloat16,
                            n_tokens=[0, 8, 8, 3, 8], atol=1e-2),
}


@pytest.mark.parametrize("name", WALK_CASES)
def test_walk_over_live_blocks_matches_xla(name, monkeypatch):
    """The kernel's tiling — every KV head a step can hold, several table
    blocks a loop turn, a walk that ends at the sequence's last block —
    against the XLA gather, in interpreter mode."""
    monkeypatch.setattr(pa, "_FORCE_INTERPRET", True)
    case = dict(_GEOM, **WALK_CASES[name])
    atol = case.pop("atol", 2e-5)
    kw = dict(case.pop("kw", {}))
    if case.pop("alibi", False):
        from deepspeed_tpu.models.transformer import alibi_slopes

        kw["alibi_slopes"] = alibi_slopes(case["H"])
    if "max_rows" in case:
        monkeypatch.setattr(pa, "MAX_QUERY_ROWS", case.pop("max_rows"))
    args, scales = _walk_case(np.random.default_rng(5), **case)
    kh_t, T = pa._tiles(
        min(case["C"], pa.MAX_QUERY_ROWS // (case["H"] // case["KH"]))
        * (case["H"] // case["KH"]), case["D"], case["KH"], case["bs"],
        case["MB"], args[0].dtype, args[1].dtype)
    assert T == 8 and (kh_t == case["KH"] or case["C"] > 1)
    out = pa.paged_attention(*args, **kw, **scales)
    f32 = [a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a
           for a in args]
    ref = pa.paged_attention_xla(*f32, **kw, **scales)
    assert out.dtype == args[0].dtype
    for i, n in enumerate(np.asarray(args[5])):
        np.testing.assert_allclose(
            np.asarray(out, np.float32)[i, :n], np.asarray(ref)[i, :n],
            atol=atol, rtol=atol, err_msg=f"row {i} of {name}")
    # a padded row (no tokens, no context) reads as zeros, not garbage
    assert not np.asarray(out, np.float32)[0].any()


def test_unallocated_slots_are_never_dereferenced(monkeypatch):
    """A table wider than the blocks in use: its −1 slots would land on
    pool block 0, which here holds NaN and belongs to nobody."""
    monkeypatch.setattr(pa, "_FORCE_INTERPRET", True)
    args, _ = _walk_case(np.random.default_rng(6), C=2, H=4, KH=2, D=64,
                         n_tokens=[0, 2, 2, 2, 2], **_GEOM)
    q, kp, vp, tbl, sp, nt = args
    ref = pa.paged_attention_xla(q, kp.at[0].set(0.0), vp.at[0].set(0.0),
                                 tbl, sp, nt)
    out = pa.paged_attention(q, kp.at[0].set(jnp.nan), vp.at[0].set(jnp.nan),
                             tbl, sp, nt)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out)[1:], np.asarray(ref)[1:],
                               atol=2e-5, rtol=2e-5)


# the shapes the benchmark's cells run: (G·C rows, D, local KH, bs, MB)
CELL_GEOMETRIES = {
    "pythia_decode": (1, 128, 16, 64, 32),
    "pythia_chunk_256": (256, 128, 16, 64, 32),
    "mistral_decode": (4, 128, 8, 64, 64),
    "mistral_chunk_256": (1024, 128, 8, 64, 64),
    "mistral_decode_tp4": (4, 128, 2, 64, 64),
    "qwen3_next_decode": (8, 256, 2, 64, 512),
    "qwen3_next_2048_row_piece": (2048, 256, 2, 64, 512),
}


@pytest.mark.parametrize("name", CELL_GEOMETRIES)
@pytest.mark.parametrize("pool", [jnp.bfloat16, jnp.int8],
                         ids=lambda d: jnp.dtype(d).name)
def test_tile_choice_stays_under_the_budget(name, pool):
    """Shapes only: the heads and blocks a step takes, and the VMEM they
    claim, at the geometries the cells run."""
    rows, D, KH, bs, MB = CELL_GEOMETRIES[name]
    kh_t, T = pa._tiles(rows, D, KH, bs, MB, jnp.bfloat16, pool)
    assert KH % kh_t == 0 and 1 <= T <= MB and T * bs <= pa.KEY_TILE
    assert pa._step_bytes(kh_t, T, rows, D, bs, jnp.bfloat16, pool) \
        <= pa.VMEM_BUDGET < 16 * 2 ** 20
    assert rows <= pa.MAX_QUERY_ROWS
    if name.endswith("decode") or "decode_" in name:
        # a one-token step takes every local head and several blocks
        assert kh_t == KH and T > 1
    if rows >= 1024:
        assert kh_t == 1


def test_put_record_counts_the_walk():
    """``engine.last_put``: the table blocks the rows' contexts fill,
    beside the slots of the bucket's tables."""
    from deepspeed_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2, RaggedInferenceEngineConfig)
    from deepspeed_tpu.models.transformer import CausalLM, TINY_TEST

    vcfg = RaggedInferenceEngineConfig(
        max_ragged_batch_size=128, max_ragged_sequence_count=4,
        max_chunk_tokens=32, kv_blocks=32, kv_block_size=8,
        max_tracked_sequences=8)
    engine = InferenceEngineV2(CausalLM(TINY_TEST), config=vcfg)
    slots = -(-TINY_TEST.max_seq_len // 8)
    engine.put([1, 2], [list(range(1, 21)), list(range(1, 10))])
    put = engine.last_put
    assert put["kv_blocks_live"] == 3 + 2            # 20 and 9 tokens
    assert put["kv_table_slots"] == put["bucket_seqs"] * slots
    engine.put([1, 2, 3], [[5], [6], list(range(1, 9))])
    put = engine.last_put
    assert put["kv_blocks_live"] == 3 + 2 + 1        # 21, 10 and 8 tokens
    assert put["kv_table_slots"] == put["bucket_seqs"] * slots
    assert put["kv_blocks_live"] * 8 >= put["kv_read_tokens"]
