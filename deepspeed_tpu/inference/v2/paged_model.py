"""Paged ragged-batch forward over a CausalLM.

Counterpart of the reference FastGen model stack
(``inference/v2/model_implementations/inference_transformer_base.py:616``
with the ragged kernel suite: ``linear_blocked_kv_rotary`` KV write,
``blocked_flash`` attention over atoms, ``logits_gather``). One jitted
function processes a mixed prefill/decode ragged batch with static shapes:

- tokens [N, C] padded chunks, per-seq ``start_pos`` (tokens already
  cached) and ``n_tokens`` (valid width) — Dynamic SplitFuse feeds both
  prompt chunks and single decode tokens through this same path; a dense
  model's chunk row and its one-token rows may also come laid end to
  end, tokens [1, C + S]: one pass over every weight, the K/V write and
  the attention once a part (``_parts``);
- paged KV cache [L, NB, KH, bs, D] with per-seq block tables. **The pool
  stays where it is**: the jit donates it, the layer scan carries it
  whole, writes are a read-modify-write of the touched blocks of that
  one buffer at (layer, block) (``kv_write.py``), and reads go through
  the Pallas paged-attention kernel (``ops/paged_attention.py``), which
  takes the stacked pool plus the layer index and walks each sequence's
  block table directly — no per-layer slab is sliced out or written
  back, no dense [N, max_ctx, KH, D] gather, no GQA ``jnp.repeat`` (the
  XLA gather formulation remains as the off-TPU fallback inside
  ``paged_attention``). The caller's ``kv_cache`` is consumed: the
  returned cache is the same memory (docs/SERVING.md "The pool
  contract");
- returns logits only at each sequence's last valid token (logits_gather);
- the serving layout of the parameters (``fuse_qkv``, built by the engine
  where it takes them): q, k and v are one stacked leaf ``wqkv`` and the
  ``qkv`` scope one matmul, so that no layer stages a projection outside
  the fusion that multiplies by it; quantized nodes and TP shards keep
  three leaves and the same body reads either (docs/SERVING.md "The
  serving parameter tree");
- weight serving (``weight_quant.py``): when the param tree holds
  blockwise-quantized ``{"qw", "qs"}`` nodes, every projection/MLP/unembed
  matmul here runs straight from the int8/fp8 representation through
  ``models/transformer._linear``'s structural dispatch →
  ``ops/quantizer.quantized_matmul`` (dequantize-in-kernel on the Pallas
  path, fused dequant-then-dot on XLA, fp32 accumulation) —
  ``forward``/``forward_verify``/prefill all ride the same quantized tree,
  and an unquantized tree compiles the historical program byte for byte.
"""

from __future__ import annotations

import math
from typing import Dict, List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.layout import Layout, with_layout_constraint

from ...models.transformer import (CausalLM, _linear, _norm, alibi_slopes,
                                   apply_rope, rope_table)
from ...ops import latent_attention, paged_attention
from .kv_quant import quantized_block_write
from .kv_write import block_write, touched_block_plan


#: the three projections a dense layer's ``qkv`` scope multiplies by
QKV_LEAVES = ("wq", "wk", "wv")
#: positions of a wide chunk whose selection a sparse layer takes at a
#: time (``PagedCausalLM._select``), and the widths (keys) its scores are
#: taken at while the context fits one (a branch a width in every sparse
#: layer of every program: two widths compile in half the time of four,
#: and half the requests' contexts stay under the narrow one)
SELECT_ROWS = 256
SELECT_WIDTHS = (16384,)
#: positions of a chunk whose block scores a block-sparse layer takes at a
#: time (``PagedCausalLM._block_selection``): every head's softmax over
#: the table's compressed keys is 101 MB of float32 at 128 positions, 32
#: heads and 6,208 kernels
BLOCK_SCORE_ROWS = 128


def fuse_qkv(params):
    """The serving layout of a dense model's parameter tree: the stacked
    ``wq`` / ``wk`` / ``wv`` [L, in, out] become one leaf ``wqkv``
    [L, in, (nh + 2 * kvh) * hd] -- q's columns, then k's, then v's -- and
    their biases, where the family has them, one ``wqkv_b``; the three
    are not kept. ``_forward`` then multiplies once and cuts the result.
    Sliced apart, the chip's compiler stages each of the three in a buffer
    of its own and transposes it, every layer of every forward wider than
    one row (tests/test_tpu_compile.py); one leaf it slices inside the
    matmul, as it does ``wo`` and the MLP's.

    A tree whose three leaves are not all arrays -- quantized
    ``{"qw", "qs"}`` nodes (weight_quant.py: a scale block would straddle
    the seams), a hybrid model's slots, one already fused -- is returned
    as it is. The layout is the serving program's: checkpoints, the train
    engine and ``CausalLM`` never see it (``split_qkv`` undoes it)."""
    layers = params["layers"]
    if not all(getattr(layers.get(n), "ndim", 0) == 3 for n in QKV_LEAVES):
        return params
    layers = dict(layers)
    layers["wqkv"] = jnp.concatenate(
        [layers.pop(n) for n in QKV_LEAVES], axis=-1)
    biases = [layers.pop(n + "_b", None) for n in QKV_LEAVES]
    if biases[0] is not None:
        layers["wqkv_b"] = jnp.concatenate(biases, axis=-1)
    return dict(params, layers=layers)


def split_qkv(cfg, params):
    """``fuse_qkv`` undone: the tree ``CausalLM`` and ``quantize_weights``
    read (one that is not fused is returned as it is)."""
    layers = params["layers"]
    if "wqkv" not in layers:
        return params
    layers = dict(layers)
    cuts = _qkv_cuts(cfg)
    for suffix in ("", "_b"):
        if "wqkv" + suffix in layers:
            parts = jnp.split(layers.pop("wqkv" + suffix), cuts, axis=-1)
            layers.update(zip((n + suffix for n in QKV_LEAVES), parts))
    return dict(params, layers=layers)


def _qkv_cuts(cfg):
    """Where q ends and where k ends among ``wqkv``'s columns."""
    return [cfg.num_heads * cfg.head_dim,
            (cfg.num_heads + cfg.kv_heads) * cfg.head_dim]


def _rows_major(y):
    """A projection's output [N, C, out] held to the layout the matmul
    writes, a position's row behind a position's row. Left free, a
    consumer that batches by head (a lightning layer's recurrence, a
    block-sparse layer's scores) has the compiler lay the output out by
    head through the matmul, and then it is the *weight* that is
    transposed to fit — taken out of its stack into a buffer and copied
    across, 32 MiB twice in front of every such matmul of every forward
    — where a step's rows are a few KiB (``_held``)."""
    return with_layout_constraint(
        y, Layout(major_to_minor=tuple(range(y.ndim))))


def _held(cfg, kind: str, rows: int):
    """``hybrid.full_qkv``'s and ``lightning_mixer``'s ``hold`` in a
    forward of ``rows`` bucket positions: which of a ``kind`` layer's
    projections (``"q"``, ``"k"``, ``"v"``, the gate's ``"g"``) are held
    to rows (``_rows_major``). Whichever side is laid out anew is
    copied, and what that costs was measured on the chip at 4,096 wide
    (PERF.md section 6, PR 46): a lightning layer's q, k and v at every
    width (0.9 ms off a 2,048-row chunk's 101, 0.4 off a two-row step's
    9.8); its gate and a block-sparse layer's four while the rows are at
    most a quarter of the weight's (another 0.3 ms off the step, 0.1 off
    a 512-row chunk; 1 ms *onto* the 2,048-row chunk each: their rows
    are relaid in float32, behind a norm or a sigmoid, more than once)."""
    names = "qkvg" if 4 * rows <= cfg.hidden_size \
        else "qkv" if kind == "lightning" else ""
    return lambda name, y: _rows_major(y) if name in names else y


def _fed_tokens(tokens, next_ids, id_slots):
    """``tokens`` with each negative first token replaced by its row's
    slot of ``next_ids``: the token a forward before this one drew on the
    device (``PagedCausalLM._forward``). In the merged layout a row's
    first token is its place among the last ``len(id_slots)`` positions."""
    if next_ids is None:
        return tokens
    with jax.named_scope("embed"):
        rows = id_slots.shape[0]
        at = (slice(None), 0) if tokens.shape[0] == rows \
            else (0, slice(-rows, None))
        first = tokens[at]
        return tokens.at[at].set(
            jnp.where(first < 0, next_ids[id_slots], first))


class _Part(NamedTuple):
    """A run of a forward's positions that ``kv_write`` and ``attend``
    take as one batch: the positions ``[lo, hi)`` of the tokens' second
    axis seen as ``shape`` = (rows, chunk), with the rows' metadata."""
    lo: int
    hi: int
    shape: Tuple[int, int]
    block_tables: jax.Array
    start_pos: jax.Array
    n_tokens: jax.Array


def _parts(tokens, start_pos, n_tokens, block_tables) -> List[_Part]:
    """A padded ``[N, C]`` batch is one part. The merged layout --
    ``tokens`` [1, C + S] beside ``S`` rows of metadata
    (``RaggedBatchWrapper.finalize_merged``) -- is two: row 0's chunk as
    ``[1, C]``, then one position a row as ``[S, 1]``, where row 0, whose
    tokens the first part holds, is a padded row: no token, no context, no
    block (a quantized write would clear the slots past its context as
    stale, and they hold what the first part has just written)."""
    N, P = tokens.shape
    S = start_pos.shape[0]
    if S == N:
        return [_Part(0, P, (N, P), block_tables, start_pos, n_tokens)]
    C = P - S
    return [_Part(0, C, (1, C), block_tables[:1], start_pos[:1],
                  n_tokens[:1]),
            _Part(C, P, (S, 1), block_tables.at[0].set(-1),
                  start_pos.at[0].set(0), n_tokens.at[0].set(0))]


def _with_draw(logits, last_logits, new_cache, next_ids, id_slots):
    """The forward's results, with the greedy draw over ``last_logits``
    [N, V] written to the rows' slots of ``next_ids`` when there is such
    a buffer (called under the ``logits`` scope)."""
    if next_ids is None:
        return logits, new_cache
    drawn = jnp.argmax(last_logits, axis=-1).astype(next_ids.dtype)
    return logits, new_cache, next_ids.at[id_slots].set(drawn)


class PagedCausalLM:
    """Wraps a CausalLM's weights with a paged ragged forward.

    ``mesh``: optional ``jax.sharding.Mesh`` with a ``tensor`` axis — TP
    serving (reference inference/v2/model_implementations/sharding/
    qkv.py:166 head split). Projections/norms partition via GSPMD from the
    param shardings; the Pallas paged-attention kernel — which GSPMD cannot
    partition — runs inside ``shard_map`` over the tensor axis on each
    device's local heads (attention is embarrassingly parallel over heads).
    """

    def __init__(self, model: CausalLM, block_size: int,
                 max_blocks_per_seq: int, mesh=None,
                 attn_impl: str = None, max_batch_tokens: int = 0):
        self.model = model
        # the most valid tokens one forward is given (the engine's
        # max_ragged_batch_size; 0: unknown): a hybrid block's sparse FFN
        # runs over that many rows, not over the padded [N, C] bucket
        self.max_batch_tokens = int(max_batch_tokens)
        self.cfg = model.cfg
        self.block_size = block_size
        self.max_blocks_per_seq = max_blocks_per_seq
        self.mesh = mesh
        self.tp = int(mesh.shape["tensor"]) if mesh is not None else 1
        if self.tp > 1:
            if self.cfg.kv_heads % self.tp or self.cfg.num_heads % self.tp:
                raise ValueError(
                    f"TP serving needs heads ({self.cfg.num_heads}) and "
                    f"kv_heads ({self.cfg.kv_heads}) divisible by the "
                    f"tensor axis ({self.tp})")
        # attention implementation via the module registry heuristics
        # (modules.py; reference heuristics.py:179) — overridable by name
        from .modules import instantiate_attn

        self._attn_raw = instantiate_attn(self.cfg, name=attn_impl)
        # the KV pool (argument 1) is donated: the returned cache is the
        # caller's buffer, written in place
        self.forward = jax.jit(self._forward, donate_argnums=(1,))
        # trailing-positions logits variant for speculative verification
        # (spec/): same forward, but the unembed runs over each row's LAST
        # ``verify_width`` positions (right-aligned) so the target's
        # greedy choice is known at every draft offset — without
        # materializing [N, C, vocab] when only K+1 << C positions matter.
        # A separate compiled program per width bucket — the default path
        # stays byte-identical.
        self.forward_verify = jax.jit(self._forward, donate_argnums=(1,),
                                      static_argnames=("verify_width",))

    def _attend(self, q, pools, layer, block_tables, start_pos, n_tokens,
                slopes, window=0):
        """Paged attention over layer ``layer`` of the stacked pools,
        shard_mapped over the tensor axis when TP>1. ``pools``: the cache
        tree — ``k``/``v`` [L, NB, KH, bs, D], plus ``k_scale``/``v_scale``
        [L, NB, KH] per-(block, kv-head) dequant scales for int8 pools
        (kv_quant.py), sharded over the kv-head axis exactly like the
        pools, so TP serving is preserved."""
        sm_scale = self.cfg.attn_scale
        quant_kw = ({"k_scale": pools["k_scale"], "v_scale": pools["v_scale"]}
                    if "k_scale" in pools else {})
        if self.tp == 1:
            return self._attn_raw(q, pools["k"], pools["v"], block_tables,
                                  start_pos, n_tokens, alibi_slopes=slopes,
                                  window=window, sm_scale=sm_scale,
                                  layer=layer, **quant_kw)
        from jax.sharding import PartitionSpec as P
        from ...compat import shard_map

        q_spec = P(None, None, "tensor", None)        # [N, C, H, D]
        kv_spec = P(None, None, "tensor", None, None)  # [L, NB, KH, bs, D]
        rep = P()

        operands = [q, pools["k"], pools["v"], layer, block_tables,
                    start_pos, n_tokens]
        in_specs = [q_spec, kv_spec, kv_spec, rep, rep, rep, rep]
        if slopes is not None:
            operands.append(slopes)
            in_specs.append(P("tensor"))
        if quant_kw:
            operands += [pools["k_scale"], pools["v_scale"]]
            in_specs += [P(None, None, "tensor")] * 2   # [L, NB, KH]

        attn = self._attn_raw
        has_slopes = slopes is not None
        has_scales = bool(quant_kw)

        def local(q, kc, vc, lyr, tbl, sp, nt, *rest):
            i = 0
            sl = None
            if has_slopes:
                sl, i = rest[0], 1
            kw = ({"k_scale": rest[i], "v_scale": rest[i + 1]}
                  if has_scales else {})
            return attn(q, kc, vc, tbl, sp, nt, alibi_slopes=sl,
                        window=window, sm_scale=sm_scale, layer=lyr, **kw)

        return shard_map(
            local, mesh=self.mesh, in_specs=tuple(in_specs),
            out_specs=q_spec, check_vma=False)(*operands)

    # ------------------------------------------------------------------
    def _forward(self, params, kv_cache, tokens, start_pos, n_tokens,
                 block_tables, state_slots=None, next_ids=None,
                 id_slots=None, verify_width: int = 0):
        """tokens [N, C]; start_pos/n_tokens [N]; block_tables [N, MB];
        ``state_slots`` [N]: a hybrid model's rows' slots in the recurrent
        state tree, which then rides in ``kv_cache`` beside the pool
        (``_forward_hybrid``); None otherwise.

        **The merged layout** (a dense model): tokens [1, C + S] beside
        ``S`` rows of start_pos / n_tokens / block_tables / id_slots -- row
        0's chunk of ``C`` positions and one position a row, laid end to
        end (``_parts``). Everything that works position by position
        (embedding, norms, the projections, the MLP, the unembedding) runs
        once over the ``C + S`` positions, one pass over each weight;
        ``kv_write`` and ``attend`` run once a part, with the shapes a
        ``[1, C]`` and an ``[S, 1]`` forward give them, on the one carried
        pool. Logits come back ``[S, V]`` in the rows' order, as from a
        padded ``[S, C]`` batch that computes ``S * C`` positions.

        ``next_ids`` [slots + 1] int32 with ``id_slots`` [N] (the engine's;
        None: a caller that keeps no such buffer): the next token of each
        sequence, drawn on the device. A row whose first token is negative
        takes ``next_ids[id_slots[row]]`` in its place -- the id an earlier
        forward drew for its sequence, which never left the device -- and
        every row writes the greedy draw over its last-position logits
        (the first of equal maxima, as ``np.argmax``) to its slot; padded
        rows point at the scratch slot behind the last. The buffer is
        returned as a third result, a new array: the one passed in stays
        readable.
        kv_cache {k,v}: [L, NB, KH, bs, D] — plus {k_scale,v_scale}
        [L, NB, KH] when the pools are int8-quantized (kv_quant.py); the
        pytree structure selects the compiled program, so the
        unquantized trace is untouched. The jitted entry points donate
        ``kv_cache``: the returned cache is its memory, updated in place.

        Returns (last_logits [N, V], new_kv_cache) — or, with static
        ``verify_width`` W > 0, (logits [N, W, V], new_kv_cache) holding
        each row's last W valid positions *right-aligned*: position
        ``W-1`` is the row's last valid token (what the default path
        gathers), ``W-1-j`` is j tokens earlier; rows shorter than W
        duplicate their first position in the left padding.
        """
        cfg = self.cfg
        if cfg.is_hybrid:
            return self._forward_hybrid(params, kv_cache, tokens, start_pos,
                                        n_tokens, block_tables, state_slots,
                                        next_ids, id_slots, verify_width)
        tokens = _fed_tokens(tokens, next_ids, id_slots)
        N, C = tokens.shape         # C: the positions a row of ``tokens``
        parts = _parts(tokens, start_pos, n_tokens, block_tables)
        bs = self.block_size
        NB = kv_cache["k"].shape[1]
        dt = cfg.dtype
        # Program scopes (docs/OBSERVABILITY.md "XLA alignment"): every
        # operation carries in its HLO op_name the part of the program
        # that caused it — embed, layers{attn_norm, qkv, kv_write, attend,
        # attn_out, mlp}, final_norm, logits — the vocabulary
        # models/transformer.py shares. What runs under ``layers`` but
        # under none of the block's scopes is the scan's own plumbing:
        # slices of the stacked norm gains and biases (a matrix is sliced
        # inside its matmul's fusion, under that matmul's scope). The
        # pools are not in it: they ride in the carry and are neither
        # sliced nor written back.
        scope = jax.named_scope

        with scope("embed"):
            x = params["embed"]["wte"][tokens].astype(dt)      # [N, C, H]
            if cfg.embedding_layernorm:
                x = _norm(x, params["embed"]["ln_w"],
                          params["embed"].get("ln_b"), cfg.norm,
                          cfg.norm_eps)
            positions = jnp.concatenate(
                [(p.start_pos[:, None] + jnp.arange(p.shape[1])[None, :]
                  ).reshape(N, -1) for p in parts], axis=1)       # [N, C]
            slopes = None
            if cfg.position == "rope":
                cos_full, sin_full = rope_table(cfg.max_seq_len, cfg.rot_dim,
                                                cfg.rope_theta)
                cos = cos_full[positions]                       # [N, C, R/2]
                sin = sin_full[positions]
            elif cfg.position == "alibi":
                # bias applied inside the paged kernel (slope · kv_position)
                slopes = alibi_slopes(cfg.num_heads)
                cos = sin = None
            else:
                x = x + params["embed"]["wpe"][positions].astype(dt)
                cos = sin = None

        # The KV write plan (kv_write.py): which pool blocks this step's
        # rows touch and where in them each row lands. Layer-invariant —
        # computed once, closed over by every scanned layer body. int8/fp8
        # pools (kv_quant.py, docs/SERVING.md "KV quantization") are
        # detected from the cache pytree, so the unquantized program holds
        # none of their code.
        quant = "k_scale" in kv_cache
        with scope("kv_write"):
            kv_plans = [touched_block_plan(p.block_tables, p.start_pos,
                                           p.n_tokens, p.shape[1], bs, NB)
                        for p in parts]

        def rope_q(q):
            if cfg.position != "rope":
                return q
            # per-(seq, pos) tables are exactly apply_rope's ndim-3 form
            # (rotate_half or GPT-J interleaved, partial rotary included)
            return apply_rope(q, cos, sin, cfg.rope_interleaved)

        def block_for(window):
            def block(carry, xs):
                # the whole cache tree rides in the carry next to x, so
                # the loop updates the one (donated) buffer in place;
                # ``layer`` says where in it this iteration works
                x, pools = carry
                lp, layer = xs
                with scope("attn_norm"):
                    h1 = _norm(x, lp["attn_norm_w"], lp.get("attn_norm_b"),
                               cfg.norm, cfg.norm_eps)
                nh, kvh, hd = cfg.num_heads, cfg.kv_heads, cfg.head_dim
                with scope("qkv"):
                    if "wqkv" in lp:    # the serving layout (fuse_qkv)
                        q, k, v = jnp.split(
                            _linear(h1, lp["wqkv"], lp.get("wqkv_b"), dt),
                            _qkv_cuts(cfg), axis=-1)
                    else:   # quantized nodes, or sharded over ``tensor``
                        q, k, v = (_linear(h1, lp[n], lp.get(n + "_b"), dt)
                                   for n in QKV_LEAVES)
                    q = rope_q(q.reshape(N, C, nh, hd))
                    k = rope_q(k.reshape(N, C, kvh, hd))
                    v = v.reshape(N, C, kvh, hd)

                # paged KV write (reference linear_blocked_kv_rotary
                # kernel): token t lands at pool[layer, block(t), :,
                # slot(t), :] — a read-modify-write of only the touched
                # blocks, in place; quantized pools dequantize, merge and
                # re-quantize them at the monotone per-block scale
                with scope("kv_write"):
                    pools = dict(pools)
                    for name, rows in (("k", k), ("v", v)):
                        for p, kv_plan in zip(parts, kv_plans):
                            part = rows[:, p.lo:p.hi].reshape(-1, kvh, hd)
                            if quant:
                                sname = name + "_scale"
                                pools[name], pools[sname] = \
                                    quantized_block_write(
                                        pools[name], pools[sname], part,
                                        kv_plan, layer)
                            else:
                                pools[name] = block_write(pools[name], part,
                                                          kv_plan, layer)

                # paged read: Pallas block-table walk over this layer of
                # the stacked pools (reference blocked_flash; Mistral
                # sliding window clamps the walk to the last W positions;
                # TP shard_maps the walk over the tensor axis; int8 pools
                # dequantize in-kernel via the scale operands)
                with scope("attend"):
                    attn = jnp.concatenate([self._attend(
                        q[:, p.lo:p.hi].reshape(*p.shape, nh, hd), pools,
                        layer, p.block_tables, p.start_pos, p.n_tokens,
                        slopes, window=window
                    ).reshape(N, p.hi - p.lo, nh * hd) for p in parts], axis=1)
                with scope("attn_out"):
                    attn_out = _linear(attn, lp["wo"], lp.get("wo_b"), dt)
                with scope("mlp"):      # norm, MLP and the residual adds
                    x = self.model._attn_mlp_merge(x, attn_out, lp, h1)
                return (x, pools), None
            return block

        with scope("layers"):
            (x, new_cache), _ = self.model._scan_layers(
                block_for, (x, dict(kv_cache)),
                (params["layers"],
                 jnp.arange(cfg.num_layers, dtype=jnp.int32)))
        with scope("final_norm"):
            x = _norm(x, params["final_norm"]["w"],
                      params["final_norm"].get("b"), cfg.norm, cfg.norm_eps)
        with scope("logits"):
            if verify_width:
                # right-aligned trailing-positions gather: row i, slot j
                # reads chunk position n_tokens[i] - W + j (clipped) — slot
                # W-1 is exactly the default path's last-token gather
                W = verify_width
                idx = jnp.clip(n_tokens[:, None] - W + jnp.arange(W)[None, :],
                               0, C - 1)                          # [N, W]
                x_v = jnp.take_along_axis(x, idx[:, :, None],
                                          axis=1)                 # [N,W,H]
                logits = self.model._unembed(params, x_v)
                return _with_draw(logits, logits[:, -1], new_cache,
                                  next_ids, id_slots)
            # logits_gather: only the last valid token per sequence
            if len(parts) == 1:
                last_idx = jnp.clip(n_tokens - 1, 0, C - 1)
                x_last = jnp.take_along_axis(x, last_idx[:, None, None],
                                             axis=1)[:, 0]
            else:
                # row 0's is in its chunk, another row's is its own place
                chunk, rows = parts[0].hi, parts[1].shape[0]
                own = chunk + jnp.arange(rows)
                x_last = x[0, own.at[0].set(
                    jnp.clip(n_tokens[0] - 1, 0, chunk - 1))]
            logits = self.model._unembed(params, x_last[:, None, :])[:, 0]
            return _with_draw(logits, logits, new_cache, next_ids, id_slots)

    # ------------------------------------------------------------------
    def _select(self, qi, wi, pool, layer, table, ctx, topk: int,
                absorbed: bool):
        """A sparse layer's selection for a forward's [N, C] positions
        (under the ``index`` scope): qi [N, C, heads, D] and wi [N, C,
        heads] the indexer's queries and head weights, ``pool`` the
        index-key pool (which holds this forward's keys already), table
        [N, MB], ctx [N, C]: the keys a position may see (itself + 1; 0:
        a padded one). ``absorbed``: ``(idx [N·C, K], n [N·C])``, each
        position's selected keys (``hybrid.index_select``); otherwise
        the mask ``keep [N, C, keys]`` int8 over the table's keys in
        whole tiles of ``latent_prefill``. Positions are scored ``Q`` of
        one sequence a kernel row, and a wide chunk ``SELECT_ROWS``
        positions at a time: the scores of 2,048 positions over 66,560
        keys would be 545 MB before the selection's own buffers."""
        from ...models import hybrid

        N, C, HI, D = qi.shape
        bs = self.block_size
        keys = table.shape[1] * bs
        Q = math.gcd(C, latent_attention.INDEX_QUERIES)
        # the scores' width follows the context: a table cut to the
        # narrowest of ``SELECT_WIDTHS`` that holds every row's context
        # (one branch a width; the selection's passes cost by the width)
        widths = [w for w in SELECT_WIDTHS if topk <= w < keys] + [keys]

        def scored(qb, wb, ctxb, width):
            """qb [N, c, heads, D] ... -> (scores, live) [N, c, width]."""
            c = qb.shape[1]
            with jax.named_scope("index_score"):
                s = latent_attention.index_score(
                    qb.reshape(N * c // Q, Q * HI, D),
                    wb.reshape(N * c // Q, Q * HI), pool, layer,
                    jnp.repeat(table[:, :width // bs], c // Q, axis=0),
                    ctxb.reshape(N * c // Q, Q).max(axis=-1), Q)
            return s.reshape(N, c, width), \
                jnp.arange(width)[None, None, :] < ctxb[:, :, None]

        def at_width(branch, qb, wb, ctxb):
            """``branch(width)(qb, wb, ctxb)`` at the context's width."""
            if len(widths) == 1:
                return branch(keys)(qb, wb, ctxb)
            longest = jnp.max(ctxb)
            return lax.switch(
                sum((longest > w).astype(jnp.int32) for w in widths[:-1]),
                [branch(w) for w in widths], qb, wb, ctxb)

        if absorbed:
            def indices(width):
                def run(qb, wb, ctxb):
                    s, live = scored(qb, wb, ctxb, width)
                    with jax.named_scope("index_select"):
                        idx, n = hybrid.index_select(s, live, topk)
                        k = min(topk, keys)
                        return jnp.pad(idx, ((0, 0), (0, 0),
                                             (0, k - idx.shape[-1]))), n
                return run

            idx, n = at_width(indices, qi, wi, ctx)
            return idx.reshape(N * C, -1), n.reshape(N * C)
        rows = math.gcd(C, SELECT_ROWS)
        tile = latent_attention.expand_tile(table.shape[1], bs)

        def mask(width):
            def run(qb, wb, ctxb):
                s, live = scored(qb, wb, ctxb, width)
                with jax.named_scope("index_select"):
                    keep = hybrid.index_keep(s, live, topk).astype(jnp.int8)
                    return jnp.pad(keep, ((0, 0), (0, 0),
                                          (0, keys + -keys % tile - width)))
            return run

        split = lambda a: jnp.moveaxis(                        # noqa: E731
            a.reshape((N, C // rows, rows) + a.shape[2:]), 1, 0)
        keep = lax.map(lambda xs: at_width(mask, *xs),
                       (split(qi), split(wi), split(ctx)))
        return jnp.moveaxis(keep, 0, 1).reshape(N, C, -1)

    def _block_compress(self, k_pool, kc_pool, layer, table, start_pos,
                        n_tokens, chunk: int):
        """The compressed keys this forward completes, written into the
        ``kc`` leaf (under ``block_compress``): kernel j — the mean of
        keys ``stride·j … stride·j + kernel − 1`` — belongs to the
        forward that brings its last key, which may be a block after the
        one it began in, and goes to row ``j % per`` of table block
        ``j // per``. ``k_pool`` holds this forward's keys already: a
        row's run of keys, from the first kernel it completes to its last
        position, is read from whole blocks of its table and every
        stride's mean is taken once. A kernel that ends at or past a
        row's valid tokens is dropped."""
        from ...models import hybrid

        z = hybrid.block_sizes(self.cfg)
        bs, MB, NB = self.block_size, table.shape[1], kc_pool.shape[1]
        N = table.shape[0]
        nk = -(-chunk // z.stride)
        width = z.stride * (nk + z.ratio - 1)
        j0 = jnp.maximum((start_pos - z.kernel + z.stride) // z.stride, 0)
        j = j0[:, None] + jnp.arange(nk)[None, :]              # [N, nk]
        ends = j * z.stride + z.kernel - 1
        valid = (ends >= start_pos[:, None]) \
            & (ends < (start_pos + n_tokens)[:, None])
        lo = j0 * z.stride
        n_blocks = (width + 2 * bs - z.stride - 1) // bs
        ids = jnp.take_along_axis(
            table, jnp.clip((lo // bs)[:, None] + jnp.arange(n_blocks),
                            0, MB - 1), axis=1)
        run = k_pool[layer, jnp.maximum(ids, 0)]       # [N, nb, KH, bs, D]
        run = run.transpose(0, 1, 3, 2, 4).reshape(
            (N, n_blocks * bs) + run.shape[2:3] + run.shape[4:])
        run = jax.vmap(lambda r, at: lax.dynamic_slice_in_dim(
            r, at, width, axis=0))(run, lo % bs)
        rows = hybrid.block_compress(z, run)                   # [N, nk, KH, D]
        block = jnp.take_along_axis(table, jnp.clip(j // z.per, 0, MB - 1),
                                    axis=1)
        # the sentinel NB: a positive out-of-range id, really dropped
        block = jnp.where(valid & (block >= 0), block, NB)
        return kc_pool.at[layer, block, j % z.per].set(rows, mode="drop")

    def _block_selection(self, q, kc_pool, layer, table, positions):
        """A block-sparse layer's selection for a forward's [N, C]
        queries q [N, C, H, D] at ``positions`` [N, C] (a padded one's is
        whatever): the table's compressed keys are scored
        (``block_score``) and the blocks chosen (``block_select``). One
        position a row -> ``(tables [N, KH, W] of pool block ids, n
        [N])``, what ``paged_attention_select`` walks; a chunk -> the
        int8 mask [N, C, KH, MB] over the table's blocks,
        ``BLOCK_SCORE_ROWS`` positions at a time."""
        from ...models import hybrid

        cfg = self.cfg
        N, C = positions.shape
        ctx = kc_pool[layer, jnp.maximum(table, 0)]    # [N, MB, per, KH, D]
        ctx = ctx.reshape((N, -1) + ctx.shape[3:])             # [N, J, KH, D]
        if C == 1:
            with jax.named_scope("block_score"):
                scores = hybrid.block_scores(cfg, q, ctx, positions)[:, 0]
            with jax.named_scope("block_select"):
                picked, n = hybrid.block_select(cfg, scores, positions[:, 0])
                return jnp.take_along_axis(
                    jnp.maximum(table, 0)[:, None, :], picked, axis=-1), n
        rows = math.gcd(C, BLOCK_SCORE_ROWS)

        def some(xs):
            qb, at = xs
            with jax.named_scope("block_score"):
                scores = hybrid.block_scores(cfg, qb, ctx, at)
            with jax.named_scope("block_select"):
                return hybrid.block_keep(cfg, scores, at).astype(jnp.int8)

        split = lambda a: jnp.moveaxis(                        # noqa: E731
            a.reshape((N, C // rows, rows) + a.shape[2:]), 1, 0)
        keep = lax.map(some, (split(q), split(positions)))
        return jnp.moveaxis(keep, 0, 1).reshape((N, C) + keep.shape[3:])

    def _forward_hybrid(self, params, cache, tokens, start_pos, n_tokens,
                        block_tables, state_slots, next_ids=None,
                        id_slots=None, verify_width: int = 0):
        """The forward of a hybrid block (``cfg.layer_pattern``,
        models/hybrid.py): the lead layers, then one scan over the
        periods, the period's layers in order inside the body. ``cache``
        holds two kinds of state and both ride in the carry, donated and
        written in place:

        - the paged pools of the attention layers, one a group of layers
          whose K/V has one lifetime (``cfg.kv_groups()``): ``k``/``v``
          [L_0, NB_0, KH, bs, D] the first group's, ``k1``/``v1`` the
          second's — the layers of a window, whose blocks behind it the
          manager hands back while the sequence lives. ``layer`` counts a
          group's own layers, and ``block_tables`` is [N, MB] for one
          group, [G, N, MB] for several: a group's write plan and its
          kernel's walk read its own table.
          A model of latent layers keeps one leaf a group instead, ``kv``
          [L, NB, bs, W]: a token's ``(c, k_r)`` row, padded to whole
          lane tiles, shared by every head (``cfg.kv_layouts``). A forward
          of at most ``ABSORB_MAX_QUERIES`` positions a row reads it
          absorbed, each position a row of the ``mla_decode`` kernel; a
          wider chunk rebuilds K/V heads from its row's live context and
          attends expanded (ops/latent_attention.py). ``"latent_window"``
          layers keep theirs in the window group, ``kv1`` at their own
          width, and both kernels' walks start at the window
          (``hybrid.absorb_limit`` says where that kind's paths cross).
          ``"latent_sparse"`` layers keep a second leaf in the first
          group, ``ki`` [L, NB, bs, index dim]: the indexer's key of
          every token, written beside its latent row (the same plan). A
          layer scores its queries against the live ones
          (``index_score``), takes the exact top-k, and attends those
          keys only: a one-position row over its gathered rows
          (``mla_sparse_decode``), a wide chunk expanded under the
          selection's mask (``mla_sparse_prefill``).
          ``"block_sparse"`` layers keep ``k`` / ``v`` and, beside them,
          ``kc`` [L, NB, per, KH, D]: the compressed keys, a kernel a
          stride, written by the forward that brings a kernel's last key
          (``_block_compress``). A layer scores its queries against the
          table's (``_block_selection``) and attends the chosen blocks: a
          one-token row through a table a K/V head
          (``paged_attention_select``: those blocks are read and no
          other), a chunk row under a block mask a query a K/V head
          (``paged_attention_masked``: every live block walked). A query
          short of ``block_dense_len`` selects every block of its past.
        - ``ssm`` [L_lin, slots + 1, HV, DK, DV] float32 and ``conv``
          [L_lin, slots + 1, K-1, CH]: the recurrent layers' state, one
          slot a sequence (``state_slots`` [N]; padded rows point at the
          scratch slot behind the last). A row with ``start_pos`` 0 starts
          from zero whatever its slot holds; any other resumes from its
          slot. Positions at or beyond ``n_tokens`` change no state.
          ``"lightning"`` layers keep ``lightning`` [L_lgt, slots + 1,
          heads, D, D] float32 by the same rules.

        Returns (last_logits [N, V], new cache) and, given ``next_ids``,
        the buffer with this forward's draws (``_forward``)."""
        from ...models import hybrid

        if verify_width:
            raise hybrid.RecurrentStateUnsupported(
                "speculative verification rolls rejected tokens back; a "
                "hybrid block's recurrent state cannot be cut at a token "
                "and its window layers' blocks may be gone")
        cfg = self.cfg
        tokens = _fed_tokens(tokens, next_ids, id_slots)
        N, C = tokens.shape
        bs = self.block_size
        dt = cfg.dtype
        scope = jax.named_scope
        pattern, lead = cfg.layer_pattern, cfg.lead_layers
        kvh, hd = cfg.kv_heads, cfg.head_dim
        # which group an attention kind's layers write and read: its
        # window's (``kv_groups``: the whole context is window 0)
        windows = [w for w, _ in cfg.kv_groups()]
        group_of = {kind: windows.index(cfg.sliding_window
                                        if kind in cfg.group_kinds(1) else 0)
                    for kind in set(pattern + lead) & set(hybrid.ATTN_SCOPE)}
        tables = [block_tables] if block_tables.ndim == 2 \
            else list(block_tables)

        with scope("embed"):
            x = params["embed"]["wte"][tokens].astype(dt)      # [N, C, H]
            if cfg.embed_scale != 1.0:
                x = x * jnp.asarray(cfg.embed_scale, dt)
            positions = start_pos[:, None] + jnp.arange(C)[None, :]
            cos_full, sin_full = rope_table(cfg.max_seq_len, cfg.rot_dim,
                                            cfg.rope_theta)
            cos, sin = cos_full[positions], sin_full[positions]
            # a latent kind rotated at a base of its own
            own_base = {}
            for kind in set(pattern + lead) & set(hybrid.LATENT_KINDS):
                z = cfg.latent_sizes(kind)
                if z.theta != cfg.rope_theta and z.theta not in own_base:
                    c_k, s_k = rope_table(cfg.max_seq_len, z.rope, z.theta)
                    own_base[z.theta] = (c_k[positions], s_k[positions])
        quant = "k_scale" in cache
        leaf = cfg.kv_layout(bs)[0][0]
        with scope("kv_write"):
            plans = [touched_block_plan(
                t, start_pos, n_tokens, C, bs,
                cache[leaf + ("" if g == 0 else str(g))].shape[1])
                for g, t in enumerate(tables)]

        def rope(t):
            return apply_rope(t, cos, sin, cfg.rope_interleaved)

        def rope_at(theta):
            if theta not in own_base:
                return rope
            return lambda t: apply_rope(t, *own_base[theta],
                                        cfg.rope_interleaved)

        valid = jnp.arange(C)[None, :] < n_tokens[:, None]      # [N, C]
        max_rows = min(N * C, self.max_batch_tokens or N * C)
        fresh = start_pos == 0

        def mixers_for(pools, first_layer):
            """The mixers of one run of layers over ``pools`` (written
            into); ``first_layer[kind]``: where the run's first layer of
            a kind sits among its group's (or the recurrent) layers."""
            def attention_mixer(kind):
                g = group_of[kind]
                sfx = str(g) if g else ""
                turn = rope if hybrid.rotates(cfg, kind) else (lambda t: t)
                window = cfg.sliding_window if kind == "window" else 0
                # the other kinds' queries go to a kernel, whose operands
                # are laid out as they are given
                hold = _held(cfg, kind, N * C) \
                    if kind == "block_sparse" else None

                def sparse_attend(q, layer):
                    """A ``"block_sparse"`` layer behind its ``kv_write``:
                    the kernels this forward ends, the selection, and
                    the attention over what it chose."""
                    with scope("block_compress"):
                        pools["kc"] = self._block_compress(
                            pools["k"], pools["kc"], layer, tables[g],
                            start_pos, n_tokens, C)
                    picked = self._block_selection(q, pools["kc"], layer,
                                                   tables[g], positions)
                    with scope("attend"):
                        if C == 1:
                            blocks, n = picked
                            return paged_attention.paged_attention_select(
                                q, pools["k"], pools["v"], blocks,
                                jnp.where(n_tokens > 0, n, 0), start_pos,
                                sm_scale=cfg.attn_scale, layer=layer)
                        return paged_attention.paged_attention_masked(
                            q, pools["k"], pools["v"], tables[g], start_pos,
                            n_tokens, picked, sm_scale=cfg.attn_scale,
                            layer=layer)

                def mixer(h1, lp, i):
                    layer = first_layer[kind] + i
                    with scope("qkv"):
                        q, k, v, gate = hybrid.full_qkv(cfg, h1, lp, turn,
                                                        hold)
                    with scope("kv_write"):
                        for name, rows in (("k" + sfx, k), ("v" + sfx, v)):
                            rows = rows.reshape(-1, kvh, hd)
                            if quant:
                                sname = name + "_scale"
                                pools[name], pools[sname] = \
                                    quantized_block_write(
                                        pools[name], pools[sname], rows,
                                        plans[g], layer)
                            else:
                                pools[name] = block_write(
                                    pools[name], rows, plans[g], layer)
                    if kind == "block_sparse":
                        attn = sparse_attend(q, layer)
                    else:
                        with scope("attend"):
                            attn = self._attend(
                                q, {"k": pools["k" + sfx],
                                    "v": pools["v" + sfx],
                                    **({"k_scale": pools["k_scale"],
                                        "v_scale": pools["v_scale"]}
                                       if quant else {})},
                                layer, tables[g], start_pos, n_tokens, None,
                                window=window)
                    with scope("attn_out"):
                        return hybrid.full_out(cfg, attn, gate, lp)
                return mixer

            def latent_mixer(kind):
                z = cfg.latent_sizes(kind)
                g = group_of[kind]
                name = "kv" + (str(g) if g else "")
                rank, H = z.kv_rank, z.heads
                # the model's one set of sizes is ``hybrid.latent_*``'s
                # default: a kind with sizes of its own names itself
                own = () if kind == "latent" else (kind,)
                sm_scale = hybrid.latent_scale(cfg, *own)
                absorbed = C <= hybrid.absorb_limit(cfg, kind)
                pad = z.width - z.dim
                turn = rope_at(z.theta)
                # what only a window kind's calls are given
                bound = {"window": z.window} if z.window else {}

                def contexts():
                    """[N, C]: each position a row of its own, its context
                    the keys up to itself, none for a padded one."""
                    at = jnp.arange(C)[None, :]
                    return jnp.where(at < n_tokens[:, None],
                                     start_pos[:, None] + at + 1, 0)

                def mixer(h1, lp, i):
                    layer = first_layer[kind] + i
                    with scope("qkv"):
                        c_q = hybrid.latent_cq(cfg, h1, lp, kind) \
                            if z.topk else None
                        q_nope, q_rope, c, k_r = hybrid.latent_qkv(
                            cfg, h1, lp, turn, *own,
                            **({"c_q": c_q} if z.topk else {}))
                        gate = hybrid.latent_gate(cfg, h1, lp)
                        if absorbed:
                            # [q~ | q_rope | 0…], laid out as the pool's rows
                            q = jnp.concatenate(
                                [hybrid.latent_absorb(cfg, q_nope, lp, *own),
                                 q_rope, jnp.zeros((N, C, H, pad), dt)],
                                axis=-1)
                    if z.topk:
                        with scope("index"), scope("index_proj"):
                            qi, ki, wi = hybrid.index_qk(cfg, h1, c_q, lp,
                                                         turn)
                    with scope("kv_write"):
                        rows = jnp.concatenate(
                            [c, k_r, jnp.zeros((N, C, pad), dt)], axis=-1)
                        pools[name] = block_write(
                            pools[name], rows.reshape(N * C, -1), plans[g],
                            layer)
                        if z.topk:
                            with scope("index_write"):
                                pools["ki"] = block_write(
                                    pools["ki"], ki.reshape(N * C, -1),
                                    plans[g], layer)
                    if z.topk:
                        with scope("index"):
                            picked = self._select(
                                qi, wi, pools["ki"], layer, tables[g],
                                contexts(), z.topk, absorbed)
                    if absorbed:
                        with scope("attend"):
                            rows_q = q.reshape(N * C, H, -1)
                            table = jnp.repeat(tables[g], C, axis=0)
                            if z.topk:
                                o_lat = latent_attention.latent_sparse_decode(
                                    rows_q, pools[name], layer, table,
                                    *picked, rank, sm_scale)
                            else:
                                o_lat = latent_attention.latent_decode(
                                    rows_q, pools[name], layer, table,
                                    contexts().reshape(N * C), rank,
                                    sm_scale, **bound)
                        with scope("attn_out"):
                            attn = hybrid.latent_unabsorb(
                                cfg, o_lat.reshape(N, C, H, rank), lp, *own)
                    else:
                        def expand(lat):
                            k_nope, v = hybrid.latent_expand(cfg, lat, lp,
                                                             *own)
                            return (k_nope.transpose(1, 0, 2),
                                    v.transpose(1, 0, 2))

                        # a chunk row at a time: each rebuilds its own
                        # context (``latent_prefill`` opens ``kv_expand``
                        # and ``attend`` itself, a turn of its loop each)
                        attn = jnp.stack([latent_attention.latent_prefill(
                            q_nope[n], q_rope[n], pools[name], layer,
                            tables[g][n], start_pos[n], n_tokens[n], expand,
                            rank, z.v, sm_scale, **bound,
                            **({"keep": picked[n]} if z.topk else {}))
                            for n in range(N)])
                    with scope("attn_out"):
                        return hybrid.latent_out(cfg, attn, lp, gate)
                return mixer

            def lightning_mixer(h1, lp, i):
                layer = first_layer["lightning"] + i
                turn = rope if hybrid.rotates(cfg, "lightning") \
                    else (lambda t: t)
                with scope("lightning_attn"):
                    state = pools["lightning"][layer, state_slots]
                    state = jnp.where(fresh[:, None, None, None], 0, state)
                    y, state = hybrid.lightning_mixer(
                        cfg, h1, lp, turn, state, n_tokens,
                        hold=_held(cfg, "lightning", N * C))
                    pools["lightning"] = pools["lightning"].at[
                        layer, state_slots].set(state)
                    return y

            def linear_mixer(h1, lp, i):
                layer = first_layer["linear"] + i
                with scope("linear_attn"):
                    tail = pools["conv"][layer, state_slots]
                    state = pools["ssm"][layer, state_slots]
                    tail = jnp.where(fresh[:, None, None], 0, tail)
                    state = jnp.where(fresh[:, None, None, None], 0, state)
                    y, tail, state = hybrid.gdn_mixer(cfg, h1, lp, tail,
                                                      state, n_tokens)
                    pools["conv"] = pools["conv"].at[
                        layer, state_slots].set(tail)
                    pools["ssm"] = pools["ssm"].at[
                        layer, state_slots].set(state)
                    return y

            return dict({kind: latent_mixer(kind)
                         if kind in hybrid.LATENT_KINDS
                         else attention_mixer(kind) for kind in group_of},
                        linear=linear_mixer, lightning=lightning_mixer)

        def period(carry, xs):
            x, pools = carry
            slots, p = xs
            pools = dict(pools)         # the mixers below write into it
            first = {kind: lead.count(kind) + p * pattern.count(kind)
                     for kind in hybrid.KINDS}
            x, _ = hybrid.run_period(cfg, x, slots, mixers_for(pools, first),
                                     valid=valid, max_rows=max_rows)
            return (x, pools), None

        slots = tuple(params["layers"][f"slot{i}"]
                      for i in range(len(pattern)))
        with scope("layers"):
            pools = dict(cache)
            if lead:
                x, _ = hybrid.run_period(
                    cfg, x, hybrid.lead_slots(cfg, params),
                    mixers_for(pools, {kind: 0 for kind in hybrid.KINDS}),
                    kinds=lead, dense=True)
            (x, new_cache), _ = lax.scan(
                period, (x, pools),
                (slots, jnp.arange(cfg.num_periods, dtype=jnp.int32)))
        with scope("final_norm"):
            x = hybrid.block_norm(cfg, x, params["final_norm"]["w"])
        with scope("logits"):
            last_idx = jnp.clip(n_tokens - 1, 0, C - 1)
            x_last = jnp.take_along_axis(x, last_idx[:, None, None],
                                         axis=1)[:, 0]
            logits = self.model._unembed(params, x_last[:, None, :])[:, 0]
            if cfg.logit_scale != 1.0:
                logits = logits * jnp.asarray(cfg.logit_scale, logits.dtype)
            return _with_draw(logits, logits, new_cache, next_ids, id_slots)
