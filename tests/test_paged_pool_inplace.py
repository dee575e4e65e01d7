"""The KV pool stays where it is (docs/SERVING.md "The pool contract").

The paged forward donates its cache, carries it whole through the layer
scan, writes the touched blocks in place (``kv_write.py``) and reads it
through the paged kernel's layer index (``ops/paged_attention.py``). Three
things are held here, on the CPU:

- the layer-indexed kernel on the stacked pool equals the per-slab call,
  layer by layer;
- the forward's logits *and* pool contents equal the dense ``CausalLM``
  under every ``_scan_mode`` the layer scan has;
- the mechanism is on: the compiled program aliases every pool leaf to its
  output and keeps no slab-sized temporary, and ``engine.put`` consumes the
  arrays it was given.

What only the chip's compiler can show — that XLA wants no other layout
for the write than the Mosaic call wants for its operand — is compiled for
a described v5e in ``tests/test_tpu_compile.py``.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.kv_quant import Q_MAX
from deepspeed_tpu.inference.v2.paged_model import PagedCausalLM
from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig
from deepspeed_tpu.ops import paged_attention as pa


# ------------------------------------------------ kernel: stacked vs per-slab

def _stacked_case(rng, L, N, C, H, KH, D, bs, MB, NB, ctx_lens, quant):
    """Stacked pools with a different content in every layer, disjoint
    block tables, and (``quant``) int8 codes with per-block scales."""
    q = jnp.asarray(rng.standard_normal((N, C, H, D)), jnp.float32)
    shape = (L, NB, KH, bs, D)
    if quant:
        kp = jnp.asarray(rng.integers(-127, 128, size=shape), jnp.int8)
        vp = jnp.asarray(rng.integers(-127, 128, size=shape), jnp.int8)
        scales = {"k_scale": jnp.asarray(
                      rng.uniform(0.005, 0.02, size=shape[:3]), jnp.float32),
                  "v_scale": jnp.asarray(
                      rng.uniform(0.005, 0.02, size=shape[:3]), jnp.float32)}
    else:
        kp = jnp.asarray(rng.standard_normal(shape), jnp.float32)
        vp = jnp.asarray(rng.standard_normal(shape), jnp.float32)
        scales = {}
    perm = rng.permutation(NB)
    tables = np.full((N, MB), -1, np.int64)
    pos, start_pos, n_tokens = 0, [], []
    for i, ctx in enumerate(ctx_lens):
        nblk = -(-ctx // bs)
        tables[i, :nblk] = perm[pos:pos + nblk]
        pos += nblk
        n_tok = min(C, ctx)
        start_pos.append(ctx - n_tok)
        n_tokens.append(n_tok)
    return (q, kp, vp, jnp.asarray(tables, jnp.int32),
            jnp.asarray(start_pos, jnp.int32),
            jnp.asarray(n_tokens, jnp.int32), scales)


STACKED_CASES = {
    # N, C, H, KH, D, bs, MB, NB, ctx_lens, window, alibi, quant
    "mha-decode": (3, 1, 4, 4, 64, 16, 4, 16, [1, 17, 50], 0, False, False),
    "gqa-chunk": (2, 8, 4, 2, 64, 16, 6, 16, [8, 40], 0, False, False),
    "gqa-window": (3, 4, 8, 2, 64, 16, 8, 32, [20, 70, 128], 24, False,
                   False),
    "mha-alibi": (3, 1, 4, 4, 64, 16, 4, 16, [5, 33, 64], 0, True, False),
    "gqa-int8": (3, 1, 8, 2, 64, 16, 4, 16, [5, 33, 64], 0, False, True),
    "mha-int8-window": (2, 4, 4, 4, 64, 16, 6, 16, [12, 90], 32, False,
                        True),
}


@pytest.mark.parametrize("impl", ["pallas", "xla"])
@pytest.mark.parametrize("case", STACKED_CASES.values(),
                         ids=STACKED_CASES.keys())
def test_layer_indexed_read_equals_per_slab(case, impl, monkeypatch):
    """``paged_attention(stacked pool, layer=l)`` is the per-slab call on
    ``pool[l]``, for every layer, with the layer a traced scalar as it is
    inside the forward's scan — kernel (interpreter) and XLA formulation."""
    monkeypatch.setattr(pa, "_FORCE_INTERPRET", impl == "pallas")
    N, C, H, KH, D, bs, MB, NB, ctx_lens, window, alibi, quant = case
    L = 3
    rng = np.random.default_rng(7)
    q, kp, vp, tbl, sp, nt, scales = _stacked_case(
        rng, L, N, C, H, KH, D, bs, MB, NB, ctx_lens, quant)
    slopes = (jnp.asarray(rng.uniform(0.01, 0.3, size=H), jnp.float32)
              if alibi else None)
    fn = pa.paged_attention if impl == "pallas" else pa.paged_attention_xla
    stacked = jax.jit(lambda layer: fn(
        q, kp, vp, tbl, sp, nt, alibi_slopes=slopes, window=window,
        layer=layer, **scales))
    for layer in range(L):
        slab = pa.paged_attention_xla(
            q, kp[layer], vp[layer], tbl, sp, nt, alibi_slopes=slopes,
            window=window, **{k: s[layer] for k, s in scales.items()})
        out = stacked(jnp.int32(layer))
        for i in range(N):
            v = int(nt[i])
            np.testing.assert_allclose(np.asarray(out)[i, :v],
                                       np.asarray(slab)[i, :v],
                                       atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("fn", [pa.paged_attention, pa.paged_attention_xla],
                         ids=["dispatch", "xla"])
def test_pool_rank_and_layer_must_agree(fn):
    rng = np.random.default_rng(0)
    q, kp, vp, tbl, sp, nt, _ = _stacked_case(rng, 2, 1, 1, 2, 2, 16, 8, 2,
                                              4, [3], False)
    with pytest.raises(ValueError, match="needs its layer"):
        fn(q, kp, vp, tbl, sp, nt)
    with pytest.raises(ValueError, match="needs the stacked"):
        fn(q, kp[0], vp[0], tbl, sp, nt, layer=1)


# ------------------------------------------- forward vs the dense CausalLM

def _tiny(sliding_window=0, **over):
    fields = dict(vocab_size=97, hidden_size=48, intermediate_size=96,
                  num_layers=4, num_heads=4, num_kv_heads=2, max_seq_len=64,
                  position="rope", sliding_window=sliding_window,
                  attention_impl="reference", dtype=jnp.float32)
    fields.update(over)
    return TransformerConfig(**fields)


def _empty_cache(cfg, NB, bs, quant, fill=0.0):
    shape = (cfg.num_layers, NB, cfg.kv_heads, bs, cfg.head_dim)
    if quant:
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(shape[:3], jnp.float32),
                "v_scale": jnp.zeros(shape[:3], jnp.float32)}
    return {"k": jnp.full(shape, fill, cfg.dtype),
            "v": jnp.full(shape, fill, cfg.dtype)}


def _pool_rows(cache, name, table, ctx):
    """Sequence content of one pool leaf through its block table:
    [L, ctx, KH, D], dequantized when the cache carries scales."""
    ids = np.asarray(table)
    ids = ids[ids >= 0]
    blocks = np.asarray(cache[name], np.float32)[:, ids]  # [L, nb, KH, bs, D]
    if name + "_scale" in cache:
        blocks = blocks * np.asarray(
            cache[name + "_scale"])[:, ids][..., None, None]
    L, nb, KH, bs, D = blocks.shape
    return blocks.transpose(0, 1, 3, 2, 4).reshape(L, nb * bs, KH, D)[:, :ctx]


@functools.lru_cache(maxsize=None)
def _dense_answers(lengths, window=0, scan_mode="auto", position="rope"):
    """The tiny model, its weights, seeded prompts of ``lengths`` and the
    dense forward's answers to them — logits at every position, K/V of
    every layer — built once a shape: the bf16 and the int8 case of a
    test compare against the same ones."""
    model = CausalLM(_tiny(window, position=position))
    model._scan_mode = scan_mode
    params = model.init(jax.random.PRNGKey(3))
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 97, size=n) for n in lengths]
    dense_logits, dense_kv = [], []
    for p in prompts:
        toks = jnp.asarray(p[None])
        dense_logits.append(np.asarray(model.apply(params, toks))[0])
        _, c = model.prefill(params, toks, model.init_cache(1, len(p)))
        dense_kv.append(c)
    return model, params, prompts, dense_logits, dense_kv


SCAN_MODES = {
    # name -> (sliding_window schedule, forced _scan_mode)
    "uniform": (0, "auto"),
    "uniform-window": (8, "auto"),
    "segments": ((0, 0, 8, 8), "segments"),
    "switch": ((0, 8, 0, 8), "switch"),
    "alternating-as-segments": ((0, 8, 0, 8), "segments"),
}


@pytest.mark.parametrize("quant", [False, True], ids=["bf16tree", "int8tree"])
@pytest.mark.parametrize("mode", SCAN_MODES.values(), ids=SCAN_MODES.keys())
def test_forward_logits_and_pool_match_dense(mode, quant):
    """Two ragged sequences, prefill in two chunks then one decode step,
    through the carried pool under each of ``_scan_layers``' three shapes:
    last-token logits equal the dense forward's, and what lies in the
    pool — every layer, through the block tables — is the dense prefill's
    K/V. Slots nobody wrote keep what they held."""
    window, scan_mode = mode
    # dense reference: logits at every position, K/V of every layer
    model, params, prompts, dense_logits, dense_kv = _dense_answers(
        (21, 13), window, scan_mode)
    cfg = model.cfg
    bs, NB, MB = 8, 12, 5
    paged = PagedCausalLM(model, bs, MB)
    tables = jnp.asarray([[4, 9, 1, -1, -1], [7, 2, -1, -1, -1]], jnp.int32)
    FILL = 7.0
    cache = _empty_cache(cfg, NB, bs, quant, fill=FILL)

    fed = [0, 0]
    # per step, the tokens each sequence feeds: a chunk, the chunk's
    # remainder (ragged: 4 beside 3), one decode token each
    for n_tok in ([16, 9], [4, 3], [1, 1]):
        C = max(n_tok)
        toks = np.zeros((2, C), np.int32)
        for i, (p, f, n) in enumerate(zip(prompts, fed, n_tok)):
            toks[i, :n] = p[f:f + n]
        logits, cache = paged.forward(
            params, cache, jnp.asarray(toks), jnp.asarray(fed, jnp.int32),
            jnp.asarray(n_tok, jnp.int32), tables)
        fed = [f + n for f, n in zip(fed, n_tok)]
        for i, f in enumerate(fed):
            np.testing.assert_allclose(
                np.asarray(logits)[i], dense_logits[i][f - 1],
                atol=5e-3 if quant else 1e-5, rtol=0)
    assert fed == [len(p) for p in prompts]

    for i, p in enumerate(prompts):
        for name in ("k", "v"):
            want = np.asarray(dense_kv[i][name])[:, 0]      # [L, T, KH, D]
            got = _pool_rows(cache, name, tables[i], len(p))
            atol = np.abs(want).max() / Q_MAX if quant else 1e-5
            np.testing.assert_allclose(got, want, atol=atol, rtol=0)
    if not quant:
        k = np.asarray(cache["k"])
        # blocks of no table are untouched, and so are the slots past a
        # sequence's end inside its last block (21 = 2 blocks + 5 slots)
        for free in (0, 3, 5, 6, 8, 10, 11):
            assert (k[:, free] == FILL).all()
        assert (k[:, 1, :, 5:] == FILL).all()
        assert (k[:, 1, :, :5] != FILL).all()


@pytest.mark.parametrize("quant", [False, True], ids=["bf16tree", "int8tree"])
@pytest.mark.parametrize("position", ["rope", "learned", "alibi"])
def test_merged_forward_logits_and_pool_match_dense(position, quant):
    """The merged layout (``tokens`` [1, C + S] beside ``S`` rows of
    metadata): one sequence prefills in two chunks of a ``[1, 16]`` part
    while two others decode beside it, a row each of the ``[4, 1]`` part
    -- last-token logits of every row equal the dense forward's, and what
    lies in the pool is the dense prefill's K/V: both parts wrote the one
    carried pool, neither the other's blocks nor a padded row's."""
    model, params, prompts, dense_logits, dense_kv = _dense_answers(
        (21, 13, 7), position=position)
    cfg = model.cfg
    bs, NB, MB = 8, 12, 5
    paged = PagedCausalLM(model, bs, MB)
    tables = jnp.asarray([[4, 9, 1, -1, -1], [7, 2, -1, -1, -1],
                          [3, -1, -1, -1, -1], [-1] * 5], jnp.int32)
    FILL = 7.0
    cache = _empty_cache(cfg, NB, bs, quant, fill=FILL)

    # the two decoding sequences' prompts but their last two tokens, padded
    fed = [0, 11, 5]
    toks = np.zeros((2, 16), np.int32)
    for i in (1, 2):
        toks[i - 1, :fed[i]] = prompts[i][:fed[i]]
    _, cache = paged.forward(params, cache, jnp.asarray(toks),
                             jnp.zeros((2,), jnp.int32),
                             jnp.asarray(fed[1:], jnp.int32), tables[1:3])
    C, S = 16, 4
    for n_chunk in (16, 5):
        flat = np.zeros((1, C + S), np.int32)
        flat[0, :n_chunk] = prompts[0][fed[0]:fed[0] + n_chunk]
        for i in (1, 2):
            flat[0, C + i] = prompts[i][fed[i]]
        logits, cache = paged.forward(
            params, cache, jnp.asarray(flat),
            jnp.asarray(fed + [0], jnp.int32),
            jnp.asarray([n_chunk, 1, 1, 0], jnp.int32), tables)
        fed = [fed[0] + n_chunk, fed[1] + 1, fed[2] + 1]
        assert logits.shape == (S, 97)
        for i, f in enumerate(fed):
            np.testing.assert_allclose(
                np.asarray(logits)[i], dense_logits[i][f - 1],
                atol=5e-3 if quant else 1e-5, rtol=0)
    assert fed == [len(p) for p in prompts]
    for i, p in enumerate(prompts):
        for name in ("k", "v"):
            want = np.asarray(dense_kv[i][name])[:, 0]      # [L, T, KH, D]
            got = _pool_rows(cache, name, tables[i], len(p))
            atol = np.abs(want).max() / Q_MAX if quant else 1e-5
            np.testing.assert_allclose(got, want, atol=atol, rtol=0)
    if not quant:
        k = np.asarray(cache["k"])
        for free in (0, 5, 6, 8, 10, 11):
            assert (k[:, free] == FILL).all()
        assert (k[:, 1, :, 5:] == FILL).all()   # past 21 = 2 blocks + 5
        assert (k[:, 3, :, 7:] == FILL).all()   # past the third's 7 tokens


# ------------------------------------------------ the mechanism is on

def _forward_args(cfg, NB, bs, MB, N, C, quant, merged=False):
    """Two rows of four tokens, padded ``[N, C]`` -- or ``merged``, the
    first row's chunk and a place a row laid end to end, [1, C + N]."""
    model = CausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    paged = PagedCausalLM(model, bs, MB)
    cache = _empty_cache(cfg, NB, bs, quant)
    args = (params, cache,
            jnp.zeros((1, C + N) if merged else (N, C), jnp.int32),
            jnp.zeros((N,), jnp.int32), jnp.full((N,), C, jnp.int32),
            jnp.tile(jnp.arange(MB, dtype=jnp.int32)[None], (N, 1)))
    return paged, args, cache


@pytest.mark.parametrize("entry", ["forward", "forward_verify", "merged"])
@pytest.mark.parametrize("quant", [False, True], ids=["bf16tree", "int8tree"])
def test_compiled_forward_aliases_the_pool(entry, quant):
    """Static: every pool leaf is an input the compiled program aliases to
    its output (donated and written in place), and the program's
    temporaries are smaller than ONE layer's slab of one leaf — so no
    slab, let alone a pool, is copied anywhere in it, not between the
    merged layout's two writes either. The pool is sized so that a slab
    dwarfs the tiny model's activations."""
    cfg = _tiny(num_layers=3, hidden_size=32, intermediate_size=64,
                vocab_size=64)
    NB, bs, MB = 4096, 8, 4
    paged, args, cache = _forward_args(cfg, NB, bs, MB, 2, 4, quant,
                                       merged=entry == "merged")
    kw = {"verify_width": 2} if entry == "forward_verify" else {}
    if entry == "merged":
        entry = "forward"
    compiled = getattr(paged, entry).lower(*args, **kw).compile()
    pool_bytes = sum(leaf.nbytes for leaf in cache.values())
    slab_bytes = cache["k"].nbytes // cfg.num_layers
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < slab_bytes, (
        f"temporaries {mem.temp_size_in_bytes} B hold a layer's slab "
        f"({slab_bytes} B): the pool is being copied")
    text = compiled.as_text()
    assert "input_output_alias" in text
    # each leaf of the cache (argument 1 of the jit) is aliased by name of
    # its flat parameter: count the aliased entries
    header = text[text.index("input_output_alias"):].split("\n", 1)[0]
    assert header.count("may-alias") + header.count("must-alias") \
        >= len(cache)


@pytest.mark.parametrize("quant", [False, True], ids=["bf16tree", "int8tree"])
def test_put_consumes_the_pool_it_was_given(quant):
    """After ``engine.put`` the arrays that were the cache are deleted —
    their memory is the new cache's — and holders that re-read
    ``state_manager.kv_cache`` see the written pool."""
    from deepspeed_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2, RaggedInferenceEngineConfig)

    model = CausalLM(_tiny())
    params = model.init(jax.random.PRNGKey(0))
    engine = InferenceEngineV2(
        model, params, RaggedInferenceEngineConfig(
            kv_block_size=8, kv_blocks=8, kv_quant_enabled=quant))
    before = dict(engine.state_manager.kv_cache)
    engine.put([1], [list(range(10))])
    assert all(leaf.is_deleted() for leaf in before.values())
    after = engine.state_manager.kv_cache
    assert set(after) == set(before)
    assert not any(leaf.is_deleted() for leaf in after.values())
    assert float(jnp.abs(after["k"].astype(jnp.float32)).max()) > 0.0
    # a second step runs on what the first handed back
    engine.put([1], [[3]])
    assert all(leaf.is_deleted() for leaf in after.values())


def test_a_fault_after_dispatch_says_the_pool_is_gone():
    """A forward that raises once it has consumed the donated pool leaves
    nothing to retry with; ``put`` says so instead of passing on a bare
    device error (a fault *before* dispatch keeps the pool and is
    retryable: tests/test_prefix_cache.py)."""
    from deepspeed_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2, RaggedInferenceEngineConfig)

    model = CausalLM(_tiny())
    engine = InferenceEngineV2(
        model, model.init(jax.random.PRNGKey(0)),
        RaggedInferenceEngineConfig(kv_block_size=8, kv_blocks=8))
    real = engine.paged.forward

    def consume_then_fail(*args):
        real(*args)
        raise RuntimeError("device fault")

    engine.paged.forward = consume_then_fail
    with pytest.raises(RuntimeError, match="consumed the donated KV pool"):
        engine.put([1], [[1, 2, 3]])
    assert engine.state_manager.get_sequence(1).seen_tokens == 0
