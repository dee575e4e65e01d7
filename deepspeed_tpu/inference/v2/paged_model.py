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

from typing import List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ...models import hybrid
from ...models.mixers import (KINDS, Fwd, block_sparse, kinds_of,
                              own_rope_bases)
from ...models.transformer import (CausalLM, _linear, _norm, alibi_slopes,
                                   apply_rope, rope_table)
from .kv_quant import quantized_block_write
from .kv_write import block_write, touched_block_plan


#: the three projections a dense layer's ``qkv`` scope multiplies by
QKV_LEAVES = ("wq", "wk", "wv")
#: the widths (keys) a learned selection's scores are taken at while the
#: context fits one (``mixers.latent.select``: a branch a width in every
#: sparse layer of every program: two widths compile in half the time of
#: four, and half the requests' contexts stay under the narrow one)
SELECT_WIDTHS = (16384,)


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


def _write_rows(pools, leaf, rows, plan, layer):
    """``rows`` [n, ...] into layer ``layer`` of ``pools[leaf]`` at the
    plan's places (``Fwd.write_rows``): a read-modify-write of the
    touched blocks, a leaf with a scale leaf beside it through
    ``quantized_block_write``."""
    scale = leaf + "_scale"
    if scale in pools:
        pools[leaf], pools[scale] = quantized_block_write(
            pools[leaf], pools[scale], rows, plan, layer)
    else:
        pools[leaf] = block_write(pools[leaf], rows, plan, layer)


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
    def _block_compress(self, k_pool, kc_pool, layer, table, start_pos,
                        n_tokens, chunk: int):
        return block_sparse.compress_written(
            self.cfg, self.block_size, k_pool, kc_pool, layer, table,
            start_pos, n_tokens, chunk)

    def _forward_hybrid(self, params, cache, tokens, start_pos, n_tokens,
                        block_tables, state_slots, next_ids=None,
                        id_slots=None, verify_width: int = 0):
        """The forward of a hybrid block (``cfg.layer_pattern``,
        models/hybrid.py): the lead layers, then one scan over the
        periods, the period's layers in order inside the body, each
        kind's layer its ``paged`` (models/mixers/, where each module
        says what its kind keeps). ``cache`` holds two kinds of state and
        both ride in the carry, donated and written in place:

        - the paged pools, one a group of layers whose per-token cache
          has one lifetime (``cfg.kv_groups()``; the leaves and a
          block's shape in each: ``cfg.kv_layouts``), the second
          group's leaves named with a ``1`` behind. ``layer`` counts a
          group's own layers, and ``block_tables`` is [N, MB] for one
          group, [G, N, MB] for several: a group's write plan and its
          kernel's walk read its own table.
        - the recurrent layers' state leaves (``hybrid.state_shapes``),
          [L_kind, slots + 1, ...]: one slot a sequence (``state_slots``
          [N]; padded rows point at the scratch slot behind the last). A
          row with ``start_pos`` 0 starts from zero whatever its slot
          holds; any other resumes from its slot. Positions at or beyond
          ``n_tokens`` change no state.

        Returns (last_logits [N, V], new cache) and, given ``next_ids``,
        the buffer with this forward's draws (``_forward``)."""
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
        kinds = kinds_of(cfg)
        # which group a kind's layers write and read: its window's
        # (``kv_groups``: the whole context is window 0)
        windows = [w for w, _ in cfg.kv_groups()]
        group_of = {kind: windows.index(cfg.sliding_window
                                        if KINDS[kind].windowed else 0)
                    for kind in kinds if KINDS[kind].pool is not None}
        # a kind that reads another's pool rows reads them where they lie
        group_of.update({kind: group_of[KINDS[kind].shares]
                         for kind in kinds if KINDS[kind].shares})
        tables = [block_tables] if block_tables.ndim == 2 \
            else list(block_tables)

        def rope_at(width, theta):
            cos, sin = rope_table(cfg.max_seq_len, width, theta)
            cos, sin = cos[positions], sin[positions]
            return lambda t: apply_rope(t, cos, sin, cfg.rope_interleaved)

        with scope("embed"):
            x = params["embed"]["wte"][tokens].astype(dt)      # [N, C, H]
            if cfg.embed_scale != 1.0:
                x = x * jnp.asarray(cfg.embed_scale, dt)
            positions = start_pos[:, None] + jnp.arange(C)[None, :]
            ropes = {cfg.rope_theta: rope_at(cfg.rot_dim, cfg.rope_theta)}
            for theta, width in own_rope_bases(cfg).items():
                ropes[theta] = rope_at(width, theta)
        leaf = cfg.kv_layout(bs)[0][0]
        with scope("kv_write"):
            plans = [touched_block_plan(
                t, start_pos, n_tokens, C, bs,
                cache[leaf + ("" if g == 0 else str(g))].shape[1])
                for g, t in enumerate(tables)]

        valid = jnp.arange(C)[None, :] < n_tokens[:, None]      # [N, C]
        max_rows = min(N * C, self.max_batch_tokens or N * C)
        fwd = Fwd(shape=(N, C), n_tokens=n_tokens, ropes=ropes,
                  start_pos=start_pos, positions=positions, block_size=bs,
                  group_of=group_of, tables=tables, plans=plans,
                  state_slots=state_slots, fresh=start_pos == 0,
                  quant="k_scale" in cache, write_rows=_write_rows,
                  attend=self._attend, select_widths=SELECT_WIDTHS)

        def mixers_of(run):
            return {kind: KINDS[kind].paged(cfg, run) for kind in kinds}

        def mixers_for(pools, first_layer):
            """The mixers of one run of layers over ``pools`` (written
            into); ``first_layer[kind]``: where the run's first layer of
            a kind sits among its group's (or the recurrent) layers."""
            return mixers_of(fwd._replace(pools=pools,
                                          first_layer=first_layer))

        if cfg.layer_runs is not None:
            return self._forward_runs(params, cache, fwd, mixers_of, x,
                                      next_ids, id_slots)
        slots = tuple(params["layers"][f"slot{i}"]
                      for i in range(len(pattern)))
        # where the scan is a loop, the routed experts stay off its xs:
        # the body indexes them in their stacks (``hybrid.expert_stacks``).
        # A scan of one period is unrolled and its slices are bitcasts
        stacks = None
        if cfg.num_periods > 1:
            slots, stacks = hybrid.expert_stacks(cfg, slots)

        def period(carry, xs):
            x, pools = carry
            slots, p = xs
            if stacks is not None:
                slots = tuple({**lp, **st} for lp, st in zip(slots, stacks))
            pools = dict(pools)         # the mixers below write into it
            first = {kind: lead.count(kind) + p * pattern.count(kind)
                     for kind in kinds}
            x, _ = hybrid.run_period(cfg, x, slots, mixers_for(pools, first),
                                     valid=valid, max_rows=max_rows,
                                     period=None if stacks is None else p)
            return (x, pools), None

        with scope("layers"):
            pools = dict(cache)
            if lead:
                x, _ = hybrid.run_period(
                    cfg, x, hybrid.lead_slots(cfg, params),
                    mixers_for(pools, dict.fromkeys(kinds, 0)),
                    kinds=lead, dense=True)
            (x, new_cache), _ = lax.scan(
                period, (x, pools),
                (slots, jnp.arange(cfg.num_periods, dtype=jnp.int32)))
        with scope("final_norm"):
            x = hybrid.final_norm(cfg, x, params["final_norm"])
        with scope("logits"):
            last_idx = jnp.clip(n_tokens - 1, 0, C - 1)
            x_last = jnp.take_along_axis(x, last_idx[:, None, None],
                                         axis=1)[:, 0]
            logits = self.model._unembed(params, x_last[:, None, :])[:, 0]
            if cfg.logit_scale != 1.0:
                logits = logits * jnp.asarray(cfg.logit_scale, logits.dtype)
            return _with_draw(logits, logits, new_cache, next_ids, id_slots)

    def _forward_runs(self, params, cache, fwd, mixers_of, x, next_ids,
                      id_slots):
        """``_forward_hybrid``'s layers and logits for a model of several
        runs of layers (``cfg.layer_runs``, ``hybrid.run_stack``), and
        **the exit**: the engine reads a row's logits at its last valid
        position alone, and behind the last layer that writes a cache or
        a state (``cfg.exit_at()``) a position's value depends on that
        position and on caches only. So a forward of ``C`` positions a
        row runs the layers in front on all ``C``, has that layer write
        its K/V for all ``C`` and attend from the row's last, and runs
        what lies behind on that one position — the value the whole
        forward would give there, no term left out; whatever was not the
        row's last position is not computed (a chunk that is not a
        prompt's last pays the tail on its one row all the same: one
        program a chunk width). A forward of one position a row runs
        every layer as it is."""
        cfg = self.cfg
        N, C = fwd.shape
        scope = jax.named_scope
        tail = None
        if cfg.exit_at() is not None:
            last_idx = jnp.clip(fwd.n_tokens - 1, 0, C - 1)

            def narrow(a):      # [N, C, ...] -> [N, 1, ...]: the last valid
                if C == 1:
                    return a
                return jnp.take_along_axis(
                    a, last_idx.reshape((N, 1) + (1,) * (a.ndim - 2)),
                    axis=1)

            tail = fwd._replace(
                shape=(N, 1), start_pos=fwd.start_pos + last_idx,
                n_tokens=jnp.minimum(fwd.n_tokens, 1),
                positions=narrow(fwd.positions), narrow=narrow)
        with scope("layers"):
            x, new_cache = hybrid.run_stack(
                cfg, x, params["layers"], fwd, mixers_of, pools=cache,
                tail=tail)
        with scope("final_norm"):
            x = hybrid.final_norm(cfg, x, params["final_norm"])
        with scope("logits"):
            if tail is None:
                x = jnp.take_along_axis(
                    x, jnp.clip(fwd.n_tokens - 1, 0, C - 1)[:, None, None],
                    axis=1)
            logits = self.model._unembed(params, x)[:, 0]
            if cfg.logit_scale != 1.0:
                logits = logits * jnp.asarray(cfg.logit_scale, logits.dtype)
            return _with_draw(logits, logits, new_cache, next_ids, id_slots)
