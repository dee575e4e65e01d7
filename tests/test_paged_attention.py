"""Pallas paged attention: kernel (interpret mode) vs XLA gather reference.

Mirrors the reference's ragged-ops kernel tests
(tests/unit/inference/kernels/ragged_ops/test_blocked_flash.py pattern:
build a paged cache + block tables, compare against a dense reference)."""

import functools
import itertools

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


# ------------------------------------------ a copy in flight across steps

# a walk's last turn starts the first turn of the grid step after it, so
# what a row reads depends on the rows beside it: 0 (a padded row), 1, 2
# and 7 turns of 8 blocks, in every order
_TURN_CTX = {0: 0, 1: 300, 2: 600, 7: 3500}
_EDGE = dict(N=4, bs=64, MB=56, NB=80)
ORDERS = list(itertools.permutations(_TURN_CTX))


def _tpu_interpreter(monkeypatch, dma="on_wait"):
    """The kernel under Pallas's TPU interpreter, not the plain one: a
    buffer starts as NaN, not zeros, and with ``on_wait`` a copy moves
    its bytes when it is waited for and not before — a start with no
    wait of its own leaves the NaN where the fold reads."""
    from jax.experimental.pallas import tpu as pltpu

    params = pltpu.InterpretParams(dma_execution_mode=dma,
                                   uninitialized_memory="nan")
    monkeypatch.setattr(pa, "_FORCE_INTERPRET", True)
    monkeypatch.setattr(pa, "_use_interpret", lambda: params)


def _edge_case(order, C=1, H=4, KH=2, D=64, seed=9, **kw):
    ctx = [_TURN_CTX[t] for t in order]
    return _walk_case(np.random.default_rng(seed), C=C, H=H, KH=KH, D=D,
                      ctx_lens=ctx, n_tokens=[min(C, c) for c in ctx],
                      **_EDGE, **kw)


def _assert_rows(out, ref, n_tokens, atol=2e-5):
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    for i, n in enumerate(np.asarray(n_tokens)):
        np.testing.assert_allclose(out[i, :n], ref[i, :n], atol=atol,
                                   rtol=atol, err_msg=f"row {i}")
        if n == 0:      # a padded row reads as zeros, whoever is beside it
            assert not out[i].any()


@pytest.mark.parametrize("order", ORDERS, ids=lambda o: "".join(map(str, o)))
def test_step_edge_in_every_order_of_turns(order, monkeypatch):
    """Copies made at their start (the plain interpreter): a zeroing or a
    copy that lands on a slot still to be folded shows here."""
    monkeypatch.setattr(pa, "_FORCE_INTERPRET", True)
    args, _ = _edge_case(order)
    assert pa._tiles(1, 64, 2, 64, 56, jnp.float32, jnp.float32) == (2, 8)
    _assert_rows(pa.paged_attention(*args), pa.paged_attention_xla(*args),
                 args[5])


def _plain(args, scales, **kw):
    return (pa.paged_attention(*args, **scales, **kw),
            pa.paged_attention_xla(*args, **scales, **kw), args[5])


def _head_groups(monkeypatch, **kw):
    # one K/V head a step of the two: the step after (n, 0) is (n, 1)
    monkeypatch.setattr(pa, "VMEM_BUDGET", 700_000)
    assert pa._tiles(2, 64, 2, 64, 56, jnp.float32, jnp.float32) == (1, 8)
    return _plain(*_edge_case(**kw))


def _pieces_cut(monkeypatch, order, **kw):
    # three pieces of 8 positions: a piece past a row's tokens is a dead
    # step between live ones
    monkeypatch.setattr(pa, "MAX_QUERY_ROWS", 16)
    ctx = [_TURN_CTX[t] for t in order]
    args, _ = _walk_case(
        np.random.default_rng(4), C=24, H=4, KH=2, D=64, ctx_lens=ctx,
        n_tokens=[min(c, n) for c, n in zip(ctx, (24, 5, 9, 17))], **_EDGE)
    return _plain(args, {})


def _by_head(monkeypatch, order, **kw):
    rng = np.random.default_rng(8)
    (q, kp, vp, *_), _ = _edge_case(order)
    blocks = jnp.asarray([-(-_TURN_CTX[t] // 64) for t in order])
    tables = jnp.asarray(rng.integers(1, _EDGE["NB"], (4, 2, 56)), jnp.int32)
    positions = jnp.asarray(rng.integers(0, 4000, 4), jnp.int32)
    call = [q, kp[None], vp[None], tables, blocks, positions]
    out = pa.paged_attention_select(*call, layer=0)
    monkeypatch.setattr(pa, "_FORCE_INTERPRET", False)
    return (out, pa.paged_attention_select(*call, layer=0),
            (blocks > 0).astype(jnp.int32))


def _masked(monkeypatch, order, **kw):
    (q, kp, vp, tbl, sp, nt), _ = _edge_case(order, C=4)
    rng = np.random.default_rng(12)
    own = np.arange(56)[None, None, None, :] == (
        (np.asarray(sp)[:, None] + np.arange(4)[None]) // 64)[:, :, None, None]
    mask = jnp.asarray((rng.random((4, 4, 2, 56)) > 0.5) | own, jnp.int8)
    return (pa.paged_attention_masked(q, kp, vp, tbl, sp, nt, mask),
            pa.paged_attention_xla(q, kp, vp, tbl, sp, nt, block_mask=mask),
            nt)


STEP_EDGE_VARIANTS = {
    "head_groups": _head_groups,
    "head_groups_chunk": functools.partial(_head_groups, C=4),
    # the first live block of the longest row is block 43, of the next 0
    "window": lambda m, **kw: _plain(*_edge_case(**kw), window=700),
    "window_chunk": lambda m, **kw: _plain(*_edge_case(C=4, **kw),
                                           window=700),
    "alibi": lambda m, **kw: _plain(
        *_edge_case(**kw),
        alibi_slopes=jnp.asarray([0.5, 0.25, 0.125, 0.0625]) / 64),
    "int8_pool": lambda m, **kw: _plain(*_edge_case(quant=jnp.int8, **kw)),
    "pieces": _pieces_cut,
    "by_head": _by_head,
    "masked": _masked,
}


@pytest.mark.parametrize("order", [(0, 7, 1, 2), (2, 0, 7, 1), (1, 2, 7, 0)],
                         ids=lambda o: "".join(map(str, o)))
@pytest.mark.parametrize("variant", STEP_EDGE_VARIANTS)
def test_step_edge_variants_pair_their_waits(variant, order, monkeypatch):
    """The body's variants across the step edge (a padded row first,
    between two live ones, last), copies made when they are waited for."""
    _tpu_interpreter(monkeypatch)
    out, ref, n_tokens = STEP_EDGE_VARIANTS[variant](monkeypatch, order=order)
    _assert_rows(out, ref, n_tokens,
                 atol=2e-4 if variant == "int8_pool" else 2e-5)


@pytest.mark.parametrize("dma", ["eager", "on_wait"])
def test_buffers_that_start_as_nan_are_never_read_unfilled(dma, monkeypatch):
    """Both slots of ``k_buf`` / ``v_buf`` start as NaN. Copies made at
    once (``eager``): the one zeroing of ``v_buf`` must not fall on a
    turn already fetched. Copies made at their wait (``on_wait``): every
    turn folded was waited for. Rows of one block each leave most of a
    turn's places unfilled, beside rows that fill every place."""
    _tpu_interpreter(monkeypatch, dma)
    ctx = [3500, 30, 600, 0, 64, 512, 1]
    args, _ = _walk_case(np.random.default_rng(2), C=1, H=4, KH=2, D=64,
                         ctx_lens=ctx, n_tokens=[1, 1, 1, 0, 1, 1, 1],
                         **dict(_EDGE, N=7))
    out = pa.paged_attention(*args)
    assert np.isfinite(np.asarray(out)).all()
    _assert_rows(out, pa.paged_attention_xla(*args), args[5])


# ------------------------------------ turns inside every row's view: no mask

# 16-token blocks and ``KEY_TILE`` patched to 128: 8 blocks, 128 keys a turn
_SEEN = dict(N=4, bs=16, MB=48, NB=160, H=4, KH=2, D=64)


def _seen_plain(monkeypatch, ctx_lens, n_tokens, C=1, max_rows=None,
                alibi=False, **kw):
    case = dict(_SEEN, C=C, ctx_lens=ctx_lens, n_tokens=n_tokens)
    quant = kw.pop("quant", None)
    if max_rows:
        monkeypatch.setattr(pa, "MAX_QUERY_ROWS", max_rows)
    if alibi:
        kw["alibi_slopes"] = jnp.asarray([0.5, 0.25, 0.125, 0.0625]) / 64
    args, scales = _walk_case(np.random.default_rng(3), quant=quant, **case)
    tile = pa._chunk_tile(C, 2)
    assert pa._tiles(2 * tile, 64, 2, 16, 48, args[0].dtype,
                     args[1].dtype)[1] == 8
    counts = pa.grid_steps(
        np.asarray(args[4]), np.asarray(args[5]), chunk=C, heads=4,
        kv_heads=2, head_dim=64, block_size=16, table_blocks=48,
        window=kw.get("window", 0), q_dtype=args[0].dtype,
        pool_dtype=args[1].dtype)
    return (lambda: pa.paged_attention(*args, **scales, **kw)), counts[2:]


def _seen_by_head(monkeypatch):
    rng = np.random.default_rng(8)
    (q, kp, vp, *_), _ = _walk_case(rng, **dict(
        _SEEN, C=1, ctx_lens=[0, 40, 330, 130], n_tokens=[0, 1, 1, 1]))
    blocks = jnp.asarray([0, 3, 20, 9])
    tables = jnp.asarray(rng.integers(1, 160, (4, 2, 48)), jnp.int32)
    positions = jnp.asarray([0, 37, 4000, 143], jnp.int32)
    # a selected table is a context of its own: rows of 3, 20 and 9
    # blocks walk 1, 3 and 2 turns a head, the last of each on the edge
    return (lambda: pa.paged_attention_select(
        q, kp[None], vp[None], tables, blocks, positions, layer=0),
        (2 * (1 + 3 + 2), 2 * (0 + 2 + 1)))


def _seen_masked(monkeypatch):
    (q, kp, vp, tbl, sp, nt), _ = _walk_case(
        np.random.default_rng(3), **dict(_SEEN, C=4, ctx_lens=[0, 40, 330, 600],
                                         n_tokens=[0, 4, 4, 4]))
    rng = np.random.default_rng(12)
    own = np.arange(48)[None, None, None, :] == (
        (np.asarray(sp)[:, None] + np.arange(4)[None]) // 16)[:, :, None, None]
    mask = jnp.asarray((rng.random((4, 4, 2, 48)) > 0.5) | own, jnp.int8)
    # a block mask is per query and per block: no turn goes unmasked
    return (lambda: pa.paged_attention_masked(q, kp, vp, tbl, sp, nt, mask),
            (None, 0))


#: name -> (builder, the turns folded without the mask: some or none)
SEEN_CASES = {
    # rows 230..277 cross key 256: the turns [128, 256) and [256, 384) are
    # on the diagonal, [0, 128) is inside every row's view
    "diagonal_straddles_two_turns": (functools.partial(
        _seen_plain, C=48, ctx_lens=[0, 278, 600, 130],
        n_tokens=[0, 48, 48, 48]), True),
    # rows 400..407 under a window of 300: the first live block is 6, a
    # place of turn [0, 128) behind six never copied; [128, 384) is inside
    # every row's window; [384, 512) is the diagonal's
    "window_first_block_mid_turn": (functools.partial(
        _seen_plain, C=8, ctx_lens=[0, 408, 700, 90], n_tokens=[0, 8, 8, 8],
        window=300), True),
    "window_decode": (functools.partial(
        _seen_plain, ctx_lens=[0, 409, 700, 90], n_tokens=[0, 1, 1, 1],
        window=300), True),
    # G·C over MAX_QUERY_ROWS (16): three pieces of 8 positions, the
    # later ones past a row's tokens
    "piece_past_a_rows_tokens": (functools.partial(
        _seen_plain, C=24, ctx_lens=[0, 300, 505, 700],
        n_tokens=[0, 24, 5, 9], max_rows=16), True),
    "pieces": (functools.partial(
        _seen_plain, C=32, ctx_lens=[400, 300, 512, 700],
        n_tokens=[32, 32, 32, 32], max_rows=16), True),
    "padded_rows_between": (functools.partial(
        _seen_plain, ctx_lens=[300, 0, 600, 0], n_tokens=[1, 0, 1, 0]), True),
    # contexts of 128 (its one turn whole and seen: the row sits on key
    # 127), 129 (one key into the second turn) and 200
    "context_ends_mid_turn": (functools.partial(
        _seen_plain, ctx_lens=[200, 129, 128, 640], n_tokens=[1, 1, 1, 1]),
        True),
    "short_chunks_see_no_whole_turn": (functools.partial(
        _seen_plain, C=8, ctx_lens=[0, 100, 8, 60], n_tokens=[0, 8, 8, 3]),
        False),
    "alibi": (functools.partial(
        _seen_plain, C=4, ctx_lens=[0, 278, 600, 130], n_tokens=[0, 4, 2, 4],
        alibi=True), True),
    "int8_pool": (functools.partial(
        _seen_plain, C=4, ctx_lens=[0, 278, 600, 130], n_tokens=[0, 4, 4, 2],
        quant=jnp.int8), True),
    "by_head": (_seen_by_head, True),
    "masked": (_seen_masked, False),
}


@pytest.mark.parametrize("name", SEEN_CASES)
def test_unmasked_turns_change_no_bit(name, monkeypatch):
    """A step with its scores in a scratch folds a turn that lies wholly
    inside every row's view without the mask (``_unmasked_span``): the
    outputs — the rows past a row's
    tokens and the padded rows too — are bit for bit those of the same
    kernel masking every turn, under the TPU interpreter (a place no
    block was copied into holds NaN: an unmasked turn never holds one)."""
    _tpu_interpreter(monkeypatch)
    monkeypatch.setattr(pa, "KEY_TILE", 128)
    monkeypatch.setattr(pa, "_stages_scores", lambda rows: True)
    pa._grid_shape.cache_clear()
    build, some = SEEN_CASES[name]
    call, (turns, unmasked) = build(monkeypatch)
    assert (unmasked > 0) == some and (turns is None or unmasked < turns)
    out = np.asarray(call())
    monkeypatch.setattr(pa, "_MASK_EVERY_TURN", True)
    ref = np.asarray(call())
    assert np.isfinite(out.astype(np.float32)).all()
    np.testing.assert_array_equal(out.view(np.uint32), ref.view(np.uint32))
    pa._grid_shape.cache_clear()


def _kernel_jaxpr(C=256, **kw):
    q = jnp.zeros((4, C, 4, 64), jnp.float32)
    kp = jnp.zeros((2, 32, 2, 16, 64), jnp.float32)
    tbl, sp = jnp.zeros((4, 16), jnp.int32), jnp.zeros((4,), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda q, kp, tbl, sp: pa._paged_pallas(
        q, kp, kp, tbl, sp, sp, layer=0, interpret=False, **kw))(
            q, kp, tbl, sp)
    call, = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    return call.params["jaxpr"]


def _primitives(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _primitives(sub)


def _count(eqns, name):
    return sum(e.primitive.name == name for e in eqns)


@pytest.mark.parametrize("kw", [{}, {"window": 40}],
                         ids=["plain", "window"])
def test_a_chunk_step_holds_one_guard_and_one_fold(kw):
    """The kernel is traced and lowered once a forward program: the rule
    costs a chunk step (512 rows a K/V head) one conditional round the
    mask and no second copy of the fold's two dots; the other
    conditional is the call's one zeroing of ``v_buf``."""
    assert pa._stages_scores(512) and not pa._stages_scores(2 * 48)
    eqns = list(_primitives(_kernel_jaxpr(**kw)))
    assert (_count(eqns, "cond"), _count(eqns, "dot_general")) == (2, 2)


@pytest.mark.parametrize("case", ["one_token", "by_head", "masked", "hook"])
def test_a_step_with_no_scratch_masks_every_turn(case, monkeypatch):
    """... and a one-token step, a block-sparse layer's two variants and
    the tests' reference run the body they ran: no guard."""
    kw = {"one_token": dict(C=1), "by_head": dict(C=1, by_head=True),
          "masked": dict(block_mask=jnp.ones((4, 2, 256, 128), jnp.int8)),
          "hook": {}}[case]
    if case == "hook":
        monkeypatch.setattr(pa, "_MASK_EVERY_TURN", True)
    eqns = list(_primitives(_kernel_jaxpr(**kw)))
    assert _count(eqns, "cond") == 1
    assert _count(eqns, "dot_general") == (3 if case == "masked" else 2)


def _turns_by_hand(rows, chunk, tile, head_groups, bs, T, slots, window=0):
    """``(turns, unmasked)`` of one call from the ``keep`` matrix
    ``paged_attention_xla`` builds, piece by piece: a turn of a live walk
    is unmasked when ``keep`` holds every (row of the piece, key of the
    turn) pair."""
    keys = T * bs
    kv = np.arange((slots // T + 1) * keys)
    turns = unmasked = 0
    for c0 in range(0, chunk, tile):
        for start, n in rows:
            n_sub = min(max(n - c0, 0), tile)
            start = start + c0 if n_sub else 0
            last = min(-(-(start + n_sub) // bs), slots)
            first = max(start - window + 1, 0) // bs if window else 0
            if last <= first:
                continue
            qpos = start + np.arange(tile)[:, None]
            keep = (qpos >= kv[None]) & (kv[None] < start + n_sub)
            if window:
                keep &= qpos - kv[None] < window
            for turn in range(first // T, -(-last // T)):
                turns += head_groups
                unmasked += head_groups * bool(
                    keep[:, turn * keys:(turn + 1) * keys].all())
    return turns, unmasked


@pytest.mark.parametrize("shape", [
    # chunk, window, heads, kv_heads, head_dim, block size, table blocks
    (1, 0, 8, 2, 128, 64, 64), (1, 700, 8, 2, 128, 64, 64),
    (64, 0, 8, 2, 128, 64, 64), (64, 1200, 8, 2, 128, 16, 128),
    (256, 0, 8, 2, 128, 64, 64), (256, 1000, 8, 2, 128, 64, 64),
    # 6 heads a K/V head: a 512-token chunk is cut into pieces of 256
    (512, 1000, 48, 8, 128, 64, 64),
], ids=["decode", "decode_window", "chunk", "chunk_window_small_blocks",
        "one_head_a_step", "one_head_a_step_window", "pieces"])
def test_grid_steps_counts_the_unmasked_turns_of_the_keep_matrix(
        shape, monkeypatch):
    """The host's twin of the kernel's rule against brute force, on
    random rows (padded ones and rows short of the chunk among them);
    a one-token step masks every turn (``_stages_scores``), so it is
    counted with that rule taken away."""
    chunk, window, heads, kv_heads, head_dim, bs, slots = shape
    if chunk == 1:
        assert pa.grid_steps(np.asarray([4000]), np.asarray([1]), chunk=1,
                             heads=heads, kv_heads=kv_heads,
                             head_dim=head_dim, block_size=bs,
                             table_blocks=slots)[2:] == (8, 0)
        monkeypatch.setattr(pa, "_stages_scores", lambda rows: True)
    pa._grid_shape.cache_clear()
    rng = np.random.default_rng(chunk + window)
    n = rng.integers(0, chunk + 1, 24)
    n[::5] = 0
    n[1::5] = chunk
    start = np.where(n > 0, rng.integers(0, slots * bs - chunk + 1, 24), 0)
    start[2::7] //= 8                       # short contexts too
    tile = pa._chunk_tile(chunk, heads // kv_heads)
    kh_t, T = pa._tiles(heads // kv_heads * tile, head_dim, kv_heads, bs,
                        slots, jnp.bfloat16, jnp.bfloat16)
    got = pa.grid_steps(start, n, chunk=chunk, heads=heads,
                        kv_heads=kv_heads, head_dim=head_dim, block_size=bs,
                        table_blocks=slots, window=window)
    want = _turns_by_hand(list(zip(start, n)), chunk, tile, kv_heads // kh_t,
                          bs, T, slots, window)
    assert got[2:] == want and 0 < want[1] < want[0]
    pa._grid_shape.cache_clear()


# ---------------------------------------------- the count of the grid steps

def _grid_by_hand(rows, head_groups, bs, slots, window=0):
    """``(steps, primed)`` of one kernel call, step by step as the grid
    runs: (n, h) is live if its walk holds a block, primed if the step
    before it was live too."""
    steps = primed = 0
    before = False
    for start, n in rows:
        for _ in range(head_groups):
            last = min(-(-(start + n) // bs), slots)
            first = max(start - window + 1, 0) // bs if window else 0
            live = last > first
            steps += live
            primed += live and before
            before = live
    return steps, primed


@pytest.mark.parametrize("case", [
    # rows' (start_pos, n_tokens), chunk, window, head groups a row
    dict(rows=[(599, 1)] * 5 + [(0, 0)] * 3, chunk=1, hg=1),
    dict(rows=[(0, 0), (40, 1), (0, 0), (7, 1), (900, 1)], chunk=1, hg=1),
    dict(rows=[(5000, 1), (100, 1), (0, 0)], chunk=1, hg=1, window=4096),
    # 8 heads over 2: G·C = 1,024 rows a K/V head, one head a step
    dict(rows=[(256, 256), (0, 17), (0, 0)], chunk=256, hg=2),
], ids=["padded_tail", "padded_between", "window", "head_groups"])
def test_grid_steps_is_the_kernels_grid_walked_by_hand(case):
    rows, chunk, hg = case["rows"], case["chunk"], case["hg"]
    window = case.get("window", 0)
    shape = dict(chunk=chunk, heads=8, kv_heads=2, head_dim=128,
                 block_size=64, table_blocks=64, window=window)
    assert pa._tiles(4 * chunk, 128, 2, 64, 64, jnp.bfloat16,
                     jnp.bfloat16)[0] == 2 // hg
    start, n = map(np.asarray, zip(*rows))
    assert pa.grid_steps(start, n, **shape)[:2] \
        == _grid_by_hand(rows, hg, 64, 64, window)


def test_grid_steps_of_a_chunk_cut_in_pieces(monkeypatch):
    """Three pieces of 8 positions, each a call of its own: a piece past
    a row's tokens is a dead step, and no step is fetched for across two
    calls."""
    monkeypatch.setattr(pa, "MAX_QUERY_ROWS", 16)
    pa._grid_shape.cache_clear()
    rows = [(100, 24), (0, 5), (30, 9), (0, 0), (64, 17)]
    start, n = map(np.asarray, zip(*rows))
    got = pa.grid_steps(start, n, chunk=24, heads=4, kv_heads=2, head_dim=64,
                        block_size=8, table_blocks=16,
                        q_dtype=jnp.float32, pool_dtype=jnp.float32)
    pieces = [[(s + c0, min(max(k - c0, 0), 8)) if k > c0 else (0, 0)
               for s, k in rows] for c0 in (0, 8, 16)]
    want = [_grid_by_hand(p, 1, 8, 16) for p in pieces]
    assert want == [(4, 2), (3, 0), (2, 0)]
    assert got[:2] == tuple(map(sum, zip(*want)))
    pa._grid_shape.cache_clear()


def test_put_record_counts_the_grid_steps(monkeypatch):
    """``attn_steps`` / ``attn_steps_primed`` of ``engine.last_put``: the
    kernel's rule on the rows a forward is handed — padded rows, a merged
    forward's two calls, a put of two forwards summed — and on the
    ``forward`` span alone, not on ``stage``."""
    from deepspeed_tpu.inference.v2.engine_v2 import (
        FORWARD_ONLY, InferenceEngineV2, RaggedInferenceEngineConfig)
    from deepspeed_tpu.models.transformer import CausalLM, TINY_TEST

    vcfg = RaggedInferenceEngineConfig(
        max_ragged_batch_size=512, max_ragged_sequence_count=8,
        max_chunk_tokens=64, kv_blocks=64, kv_block_size=8,
        max_tracked_sequences=16)
    # off the chip the registry takes the XLA gather: no grid, no count
    assert "attn_steps" not in _put(InferenceEngineV2(
        CausalLM(TINY_TEST), config=vcfg), [1], [[5, 6, 7]])
    monkeypatch.setattr(pa, "_FORCE_INTERPRET", True)
    engine = InferenceEngineV2(CausalLM(TINY_TEST), config=vcfg)
    assert "attn_steps".startswith(FORWARD_ONLY)
    # ... and an untraced forward does not pay for the count
    assert "attn_steps" not in _put(engine, [9], [[5, 6, 7]])
    from deepspeed_tpu.telemetry import Tracer

    engine.tracer = Tracer()
    # three rows in a bucket of four: the fourth is a padded row
    put = _put(engine, [1, 2, 3], [list(range(1, 21)), [4] * 9, [7] * 3])
    assert (put["bucket_seqs"], put["attn_steps"],
            put["attn_steps_primed"]) == (4, 3, 2)
    # a row of 40, four one-token rows and a row of two: past 128
    # positions the first chunk and the ones are one merged forward of two
    # calls — the chunk's one step, then a padded row 0 before four live
    # rows (and three padded) — and the second chunk a forward of its own
    put = _put(engine, [4, 1, 2, 3, 5, 6],
               [list(range(1, 41)), [9], [9], [9], [8] * 2, [8]])
    assert engine.put_totals["forwards_merged"] == 1 and put["forwards"] == 2
    assert (put["attn_steps"], put["attn_steps_primed"]) \
        == (1 + 4 + 1, 0 + 3 + 0)
    # the scheduler's spans: on ``forward``, not on ``stage``
    from deepspeed_tpu.inference.v2.scheduler import \
        ContinuousBatchingScheduler

    tracer = Tracer()
    sched = ContinuousBatchingScheduler(engine, tracer=tracer)
    for uid in (11, 12):
        sched.submit(uid, [3] * 12, max_new_tokens=2)
    while sched.has_work:
        sched.step()
    spans = tracer.export()
    counts = [s["attrs"] for s in spans if s["name"] == "forward"]
    assert counts and all(a["attn_steps"] == a["rows"] for a in counts)
    assert [a["attn_steps_primed"] for a in counts] \
        == [a["rows"] - 1 for a in counts]
    assert not any(k.startswith("attn_steps") for s in spans
                   if s["name"] == "stage" for k in s["attrs"])


def test_put_record_counts_the_turns_folded_unmasked(monkeypatch):
    """``attn_turns`` / ``attn_turns_unmasked`` ride beside ``attn_steps``:
    on a traced forward's record and its ``forward`` span, summed over a
    put's forwards, and an untraced forward pays nothing for them."""
    from deepspeed_tpu.inference.v2.engine_v2 import (
        FORWARD_ONLY, InferenceEngineV2, RaggedInferenceEngineConfig)
    from deepspeed_tpu.models.transformer import CausalLM, TINY_TEST
    from deepspeed_tpu.telemetry import Tracer

    monkeypatch.setattr(pa, "_FORCE_INTERPRET", True)
    monkeypatch.setattr(pa, "KEY_TILE", 16)        # two blocks of 8 a turn
    monkeypatch.setattr(pa, "_stages_scores", lambda rows: True)
    pa._grid_shape.cache_clear()
    engine = InferenceEngineV2(CausalLM(TINY_TEST), config=(
        RaggedInferenceEngineConfig(
            max_ragged_batch_size=512, max_ragged_sequence_count=8,
            max_chunk_tokens=64, kv_blocks=64, kv_block_size=8,
            max_tracked_sequences=16)))
    assert "attn_turns".startswith(FORWARD_ONLY)
    calls = []
    grid_steps = pa.grid_steps
    monkeypatch.setattr(pa, "grid_steps", lambda *a, **k: (
        calls.append(1), grid_steps(*a, **k))[1])
    assert "attn_turns" not in _put(engine, [1], [list(range(1, 41))])
    assert not calls                    # untraced: the count is never made
    engine.tracer = Tracer()
    # a prompt of 40 from position 0: its three turns all hold the diagonal
    put = _put(engine, [2], [list(range(1, 41))])
    assert (put["attn_turns"], put["attn_turns_unmasked"]) == (3, 0)
    # one token each at positions 40: [0, 16) and [16, 32) are wholly seen,
    # [32, 48) holds the row's own key and the context's end; two padded rows
    put = _put(engine, [1, 2], [[7], [7]])
    assert (put["bucket_seqs"], put["attn_steps"], put["attn_turns"],
            put["attn_turns_unmasked"]) == (2, 2, 6, 4)
    tracer = Tracer()
    from deepspeed_tpu.inference.v2.scheduler import \
        ContinuousBatchingScheduler

    sched = ContinuousBatchingScheduler(engine, tracer=tracer)
    sched.submit(11, [3] * 20, max_new_tokens=3)
    while sched.has_work:
        sched.step()
    spans = tracer.export()
    counts = [s["attrs"] for s in spans if s["name"] == "forward"]
    # the prompt's forward, then one-token forwards at 20, 21: [0, 16) seen
    assert [(a["attn_turns"], a["attn_turns_unmasked"]) for a in counts] \
        == [(2, 0), (2, 1), (2, 1)]
    assert not any(k.startswith("attn_turns") for s in spans
                   if s["name"] == "stage" for k in s["attrs"])
    pa._grid_shape.cache_clear()


def _put(engine, uids, tokens):
    engine.put(uids, tokens)
    return dict(engine.last_put)
