"""The parts of a hybrid block: layers of more than one kind in one model
(``TransformerConfig.layer_pattern``: one period of mixer kinds, scanned
one period an iteration — in serving too, however few the periods: two
periods laid out inline ran slower than the loop in every program
measured, PERF.md section 6, PR 46; where that loop runs more than once
the serving forward keeps the routed experts off its ``xs``:
``expert_stacks``). A position of the period is a mixer
followed by the FFN, a mixer alone (``layer_ffn`` False there) or an FFN
alone (None in the pattern): one norm and one residual add for each part
it has, so a model that counts its mixers and its FFNs as layers of
their own is a period of such positions.

- the mixers: one module a family under ``models/mixers/``, each kind
  described there (its weights, its cache, its two forwards, its
  counters) and listed in ``mixers.KINDS``. What follows folds over a
  model's kinds and names none.
- the FFN: dropless top-k experts over a held range plus a shared expert
  (``moe/grouped.py``): softmax or sigmoid scores, a selection bias
  outside the weights, the shared expert under a sigmoid gate or bare;
  gated experts (SiLU, or ReLU: ``reglu``) or the ungated ``relu2``
  (``moe_activation``); the routed experts on the model's width or in a
  latent between two projections (``moe_latent_size``); the router on
  the FFN's normed input or on the layer's own input, ahead of the
  mixer (``moe_router_input``).
  ``lead_layers`` run before the scanned periods with a dense MLP in its
  place, and so does every layer of a model without experts
  (``moe_num_experts`` 0); ``sandwich_norm`` puts a norm behind the mixer
  and the FFN too; ``residual_scale`` multiplies both before their adds.
- the norm: RMSNorm, or LayerNorm with gain and bias (``cfg.norm``:
  ``norm_of``).
- a model of several runs of layers (``cfg.layer_runs``: ``run_stack``):
  run after run, each a pattern with periods of its own and a scan of its
  own, with values that ride beside ``x`` from a run to the runs behind
  it (a state-space layer's memory, a whole-context layer's K/V) and, in
  serving, an exit behind the last layer that writes a cache.

``CausalLM`` (training, the reference path) and ``PagedCausalLM``
(serving) both run their layers through ``run_period``; only where the
mixer's cache lives differs (a kind's ``reference`` and ``paged``).
Scopes follow ``docs/OBSERVABILITY.md``: ``attn_norm``, the kind's own
(its module says), and ``mlp`` ⊃ ``router``, ``latent_proj``, ``experts``,
``shared_expert`` or ``dense_mlp`` (``router`` stands ahead of
``attn_norm``, beside it, where ``moe_router_input`` is "layer").
"""

from __future__ import annotations

import contextlib
import itertools
import math

import jax
import jax.numpy as jnp

from ..moe.grouped import GATED, dropless_moe_mlp
from ..parallel.sharding import spec
from .mixers import KINDS, kinds_of
# (the kinds' functions that the benchmark's block tests reach under this
# module's name: tests/benchmark/test_*_block.py)
from .mixers.attention import full_qkv  # noqa: F401
from .mixers.base import block_norm, rms  # noqa: F401
from .mixers.block_sparse import (block_compress, block_keep,  # noqa: F401
                                  block_scores, block_select, block_sizes)
from .mixers.latent import (index_qk, index_scores, latent_cq,  # noqa: F401
                            latent_qkv, latent_scale)
from .mixers.select import index_keep, index_select  # noqa: F401
from .transformer import _linear, _norm


class RecurrentStateUnsupported(NotImplementedError):
    """Raised where a feature that assumes per-token KV (rollback, prefix
    sharing, the KV tier, head-split TP) meets a model with recurrent
    layers: their state cannot be cut at a token or shared by prefix."""


class ReleasedKVUnsupported(NotImplementedError):
    """Raised where a feature that assumes a sequence's whole context is
    resident in one pool (the prefix cache and the KV tier over several
    layer groups, export / import and the preemption stash, a rollback
    past a released block, quantized pools of several groups) meets K/V
    kept by layer group, whose window groups hand blocks back while the
    sequence lives (inference/v2/ragged/manager.py)."""


class LatentKVUnsupported(NotImplementedError):
    """Raised where a feature that assumes K/V kept by kv-head (quantized
    pools, whose scales are one a block a kv-head; the KV tier; TP
    serving, which splits the pool and the attention by head) meets
    latent attention, whose cache is one row a token shared by every
    head (inference/v2/ragged/manager.py)."""


class CompressedKeysUnsupported(NotImplementedError):
    """Raised where a feature that assumes a pool block holds its own
    tokens' K/V and nothing else (the prefix cache, which shares blocks;
    quantized pools and TP serving, which scale and split by kv-head; the
    KV tier) meets a block-sparse layer's compressed keys, kept beside
    k / v in the same blocks (inference/v2/ragged/manager.py)."""


def state_shapes(cfg, slots: int):
    """The recurrent cache for ``slots`` sequences, by the kinds the
    model has: each one's state leaves, name -> (shape, dtype)."""
    shapes = {}
    for kind in kinds_of(cfg):
        if KINDS[kind].state is not None:
            shapes.update(KINDS[kind].state(cfg, slots))
    return shapes


# ------------------------------------------------------------------- init

class _Draw:
    """``init_slot``'s ``w``: ``w(shape, scale)`` draws a leaf stacked
    over the periods at the slot's next key."""

    std = 0.02

    def __init__(self, cfg, key, periods: int):
        self.periods = periods
        self.out_std = self.std / math.sqrt(2 * cfg.num_layers)
        # (a kind with more leaves than sixteen draws on from a second
        # split: the first sixteen are what they were)
        self._keys = itertools.chain(
            jax.random.split(key, 16),
            jax.random.split(jax.random.fold_in(key, 1), 16))

    def key(self):
        return next(self._keys)

    def __call__(self, shape, scale=std):
        return (scale * jax.random.normal(self.key(), (self.periods,) + shape)
                ).astype(jnp.float32)


def _norm_names(cfg, kind, ffn: bool, leaf: str = "w"):
    """A position's norm gains (``leaf`` "b": a LayerNorm's biases): one
    (two under ``sandwich_norm``) for each part it has."""
    parts = (("attn",) if kind is not None else ()) + (("mlp",) if ffn
                                                       else ())
    return [f"{pre}{part}_norm_{leaf}" for part in parts
            for pre in (("", "post_") if cfg.sandwich_norm else ("",))]


def norm_of(cfg):
    """The block's norm, ``(x, lp, name) -> x normed`` by the leaves
    ``lp[name + "_w"]`` and, for a LayerNorm, ``lp[name + "_b"]``."""
    if cfg.norm == "rmsnorm":
        return lambda x, lp, name: block_norm(cfg, x, lp[name + "_w"])
    return lambda x, lp, name: _norm(x, lp[name + "_w"], lp[name + "_b"],
                                     cfg.norm, cfg.norm_eps)


def final_norm(cfg, x, leaves):
    """The norm in front of the logits, by ``params["final_norm"]``."""
    return norm_of(cfg)(x, {f"final_{k}": a for k, a in leaves.items()},
                        "final")


def init_slot(cfg, kind, key, periods: int, dense: bool = False,
              ffn: bool = True):
    """One position of the period: its mixer's (``kind`` None: it has
    none) and, with ``ffn``, its FFN's weights, stacked over the
    periods. ``dense``: a lead layer, whose FFN is the dense MLP."""
    h, P = cfg.hidden_size, periods
    w = _Draw(cfg, key, periods)
    out_std = w.out_std
    fill = jnp.zeros if cfg.norm_zero_centered else jnp.ones

    def gain(*shape):
        return fill((P,) + shape, jnp.float32)

    lp = {name: gain(h) for name in _norm_names(cfg, kind, ffn)}
    if cfg.norm == "layernorm":
        lp.update({name: jnp.zeros((P, h), jnp.float32)
                   for name in _norm_names(cfg, kind, ffn, "b")})
    if kind is not None:
        lp.update(KINDS[kind].init(cfg, w, gain))
    if not ffn:
        return lp
    if dense or not cfg.moe_num_experts:
        m = cfg.intermediate_size
        lp.update(w_in=w((h, m)), w_gate=w((h, m)), w_out=w((m, h), out_std))
        return lp
    n_held = cfg.moe_held_experts[1] if cfg.moe_held_experts \
        else cfg.moe_num_experts
    m = cfg.moe_intermediate_size or cfg.intermediate_size
    gated = cfg.moe_activation in GATED
    # the experts' width: the model's, or the latent's
    he = cfg.moe_latent_size or h
    lp["router_wg"] = w((h, cfg.moe_num_experts), 1.0 / math.sqrt(h))
    lp["w_in"] = w((n_held, he, m))
    if gated:
        lp["w_gate"] = w((n_held, he, m))
    lp["w_out"] = w((n_held, m, he), out_std)
    if cfg.moe_select_bias:
        lp["router_b"] = jnp.zeros((P, cfg.moe_num_experts), jnp.float32)
    ms = cfg.moe_shared_intermediate_size
    if ms:
        lp["shared_w_in"] = w((h, ms))
        if gated:
            lp["shared_w_gate"] = w((h, ms))
        lp["shared_w_out"] = w((ms, h), out_std)
        if cfg.moe_shared_gate:
            lp["shared_gate_w"] = w((h, 1), 1.0 / math.sqrt(h))
    if cfg.moe_latent_size:
        lp.update(latent_w_in=w((h, he)), latent_w_out=w((he, h), out_std))
    return lp


def slot_specs(cfg, kind, dense: bool = False, ffn: bool = True):
    """Logical sharding axes of ``init_slot``'s tree."""
    lp = {name: spec("layers", "embed")
          for leaf in (("w", "b") if cfg.norm == "layernorm" else ("w",))
          for name in _norm_names(cfg, kind, ffn, leaf)}
    if kind is not None:
        lp.update(KINDS[kind].specs(cfg))
    if not ffn:
        return lp
    if dense or not cfg.moe_num_experts:
        lp.update(w_in=spec("layers", "embed", "mlp"),
                  w_gate=spec("layers", "embed", "mlp"),
                  w_out=spec("layers", "mlp", "embed"))
        return lp
    gated = cfg.moe_activation in GATED
    # (experts in a latent: its width is no axis of the mesh)
    wide = None if cfg.moe_latent_size else "embed"
    lp.update(router_wg=spec("layers", "embed", None),
              w_in=spec("layers", "expert", wide, "mlp"),
              w_out=spec("layers", "expert", "mlp", wide))
    if gated:
        lp["w_gate"] = spec("layers", "expert", wide, "mlp")
    if cfg.moe_select_bias:
        lp["router_b"] = spec("layers", None)
    if cfg.moe_shared_intermediate_size:
        lp.update(shared_w_in=spec("layers", "embed", "mlp"),
                  shared_w_out=spec("layers", "mlp", "embed"))
        if gated:
            lp["shared_w_gate"] = spec("layers", "embed", "mlp")
        if cfg.moe_shared_gate:
            lp["shared_gate_w"] = spec("layers", "embed", None)
    if cfg.moe_latent_size:
        lp.update(latent_w_in=spec("layers", "embed", None),
                  latent_w_out=spec("layers", None, "embed"))
    return lp


# ----------------------------------------------------------------- period

def run_period(cfg, x, slots, mixers, kinds=None, dense=False, valid=None,
               max_rows=None, transform=None, period=None, narrow=None):
    """A run of layers on x [B, T, H] — one period (``kinds`` None: the
    pattern) or the lead layers (``dense``) — each position's norm, its
    mixer, the FFN and the residual adds (a position of the pattern may
    lack its mixer or its FFN: ``cfg.layer_ffn``). ``slots``: the
    weights, one tree a position. ``mixers[kind](h1, lp, i)`` -> the
    mixer's output for the i-th layer of its kind in the run: where the
    cache lives is the caller's (training keeps none, serving paged pools
    and state slots). ``period``: the slots' routed experts are whole
    stacks over the periods (``expert_stacks``) and this is the period to
    run. ``narrow``: the run is the one layer behind whose mixer a serving
    forward's rows leave (``run_stack``): ``x`` [B, T, H] -> [B, 1, H],
    taken where the mixer's output, one position a row, is added. Returns
    (x, summed aux loss)."""
    scope = jax.named_scope
    norm = norm_of(cfg)
    aux = jnp.zeros((), jnp.float32)
    # the pattern's positions say which carry an FFN; a lead layer does
    ffns = cfg.layer_ffn if kinds is None else None
    kinds = cfg.layer_pattern if kinds is None else kinds
    ffns = ffns or (True,) * len(kinds)
    seen = {kind: 0 for kind in KINDS}
    scaled = (lambda y: y) if cfg.residual_scale == 1.0 else (
        lambda y: y * jnp.asarray(cfg.residual_scale, y.dtype))
    early_router = cfg.moe_router_input == "layer" and not dense
    for kind, ffn, lp in zip(kinds, ffns, slots):
        if transform is not None:
            lp = transform(lp)
        router_logits = None
        if early_router:        # from the layer's input, as it comes in
            with scope("router"):
                router_logits = _router_logits(
                    x.reshape(-1, x.shape[-1]), lp)
        if kind is not None:
            with scope("attn_norm"):
                h1 = norm(x, lp, "attn_norm")
            with scope(KINDS[kind].scope) if KINDS[kind].scope \
                    else contextlib.nullcontext():
                y = mixers[kind](h1, lp, seen[kind])
            seen[kind] += 1
            if narrow is not None:
                x = narrow(x)
        with scope("mlp"):      # norms, FFN and the residual adds
            if kind is not None:
                if cfg.sandwich_norm:
                    y = norm(y, lp, "post_attn_norm")
                x = x + scaled(y)
            if not ffn:
                continue
            h2 = norm(x, lp, "mlp_norm")
            if dense or not cfg.moe_num_experts:
                f, a = dense_ffn(cfg, h2, lp), 0.0
            else:
                f, a = moe_ffn(cfg, h2, lp, valid=valid, max_rows=max_rows,
                               router_logits=router_logits, period=period)
            if cfg.sandwich_norm:
                f = norm(f, lp, "post_mlp_norm")
            x = x + scaled(f)
        aux = aux + a
    return x, aux


def run_slots(cfg, layers):
    """A model of ``layer_runs``' weights, one tuple of trees a run (a
    tree a position of the run's pattern, stacked over its periods):
    ``layers["run<r>_slot<i>"]``."""
    return tuple(tuple(layers[f"run{r}_slot{i}"]
                       for i in range(len(pattern)))
                 for r, (pattern, _) in enumerate(cfg.layer_runs))


def run_stack(cfg, x, layers, fwd, mixers_of, pools=None, tail=None,
              transform=None):
    """The layers of a model that is several runs (``cfg.layer_runs``) on
    x [B, T, H]: run after run, a run of more than one period a
    ``lax.scan`` over its periods (PERF.md section 6, PR 46: a period
    inline ran slower than the loop), a run of one laid out inline.

    ``fwd``: the forward's ``mixers.Fwd``; ``mixers_of(fwd) -> {kind:
    mixer}`` builds a run's layers over it (a kind's ``reference`` or
    ``paged``) once the run's own fields are filled in here: ``pools``
    (serving: the cache tree, written into and returned), where the run's
    first layer of a kind sits (``first_layer``), its layers' depth in
    the model (``depth_of``), and the **carry** — the values that ride
    beside ``x`` from the run that hands them on to the runs that take
    them (``cfg.run_feeds``): written by the handing run's layers, read
    by the later ones.

    ``tail`` (serving): this forward as the rows behind the exit see it —
    one position a row, the row's last valid one — with ``tail.narrow``
    [B, T, ...] -> [B, 1, ...]. The layer at ``cfg.exit_at()`` writes its
    cache from every position and attends from that one
    (``Fwd.exit``); behind it ``x``, the carry and every layer run on one
    position a row, the runs behind its own under the scope ``xdec``.
    None: every position runs every layer. Returns (x, pools)."""
    scope = jax.named_scope
    kinds = kinds_of(cfg)
    feeds = cfg.run_feeds(cached=pools is not None)
    exit_at = cfg.exit_at() if tail is not None else None
    carry, first, depth = {}, dict.fromkeys(kinds, 0), 0
    pools = None if pools is None else dict(pools)

    def run_of(view, pattern, base, pools, first, hand=(), period=0):
        """``view`` filled in for one period of a run."""
        places = {kind: [i for i, k in enumerate(pattern) if k == kind]
                  for kind in dict.fromkeys(pattern)}
        return view._replace(
            pools=pools, carry=carry, hand=hand,
            first_layer={kind: first[kind] + period * pattern.count(kind)
                         for kind in kinds},
            depth_of=lambda kind, i: base + period * len(pattern)
            + places[kind][i])

    for r, ((pattern, periods), slots) in enumerate(zip(
            cfg.layer_runs, run_slots(cfg, layers))):
        behind = exit_at is not None and r > exit_at[0]
        view = tail if behind else fwd
        with scope("xdec") if behind else contextlib.nullcontext():
            if periods > 1:
                def period(c, xs, view=view, pattern=pattern, base=depth,
                           first=dict(first)):
                    x, pools = c
                    slots, p = xs
                    pools = None if pools is None else dict(pools)
                    x, _ = run_period(
                        cfg, x, slots, mixers_of(run_of(
                            view, pattern, base, pools, first, period=p)),
                        kinds=pattern, transform=transform)
                    return (x, pools), None

                (x, pools), _ = jax.lax.scan(
                    period, (x, pools),
                    (slots, jnp.arange(periods, dtype=jnp.int32)))
            else:
                # inline, in up to three parts: the layers in front of the
                # exit, the one at it, the ones behind
                slots = tuple(jax.tree.map(lambda a: a[0], lp)
                              for lp in slots)
                e = exit_at[1] if exit_at and exit_at[0] == r else None
                cuts = [(0, len(pattern), view, None)] if e is None else [
                    (0, e, fwd, None),
                    (e, e + 1, fwd._replace(exit=tail), tail.narrow),
                    (e + 1, len(pattern), tail, None)]
                at = dict(first)
                for lo, hi, part, narrow in cuts:
                    if lo == hi:
                        continue
                    x, _ = run_period(
                        cfg, x, slots[lo:hi], mixers_of(run_of(
                            part, pattern[lo:hi], depth + lo, pools, at,
                            hand=feeds[r])),
                        kinds=pattern[lo:hi], transform=transform,
                        narrow=narrow)
                    if narrow is not None:      # what rides on leaves too
                        carry = {name: jax.tree.map(narrow, value)
                                 for name, value in carry.items()}
                    for kind in pattern[lo:hi]:
                        at[kind] += 1
        for kind in kinds:
            first[kind] += periods * pattern.count(kind)
        depth += periods * len(pattern)
    return x, pools


#: the routed experts' leaves of a sparse position
EXPERT_LEAVES = ("w_in", "w_gate", "w_out")


def expert_stacks(cfg, slots):
    """The pattern's ``slots`` (every leaf stacked over the P periods) in
    two, for a scan over the periods that calls a kernel: ``(scanned,
    stacks)``, one tree a position each. ``stacks`` holds a sparse
    position's routed experts (``EXPERT_LEAVES``), each [P, n, k, m]
    stack as [P · n, k, m] (a bitcast: the two minor dimensions carry the
    tiling) for the scan to close over -- its body merges them back into
    the period's tree and runs it with ``run_period(period=p)``, and the
    grouped matmul indexes period p's experts where they lie.
    ``scanned`` is everything else, the scan's ``xs``: norms, the router,
    the mixer's and the shared expert's weights feed XLA dots, which
    absorb the scan's ``dynamic-slice``; a Mosaic kernel's operand cannot,
    so a scanned expert leaf is copied out of its stack, whole, in front
    of every ``gmm`` (PERF.md section 6, PR 56)."""
    sparse = [bool(cfg.moe_num_experts) and ffn
              for ffn in cfg.layer_ffn or (True,) * len(slots)]
    stacks = tuple(
        {name: lp[name].reshape((-1,) + lp[name].shape[2:])
         for name in EXPERT_LEAVES if here and name in lp}
        for lp, here in zip(slots, sparse))
    scanned = tuple({name: a for name, a in lp.items() if name not in st}
                    for lp, st in zip(slots, stacks))
    return scanned, stacks


def lead_slots(cfg, params):
    """The lead layers' trees, in order (each is stacked over one
    "period", like a slot: the leading dim is dropped)."""
    return tuple(jax.tree.map(lambda a: a[0], params["layers"][f"lead{j}"])
                 for j in range(len(cfg.lead_layers)))


# -------------------------------------------------------------------- FFN

def dense_ffn(cfg, h2, lp):
    """The dense gated MLP on its normed input [B, T, H]: a lead layer's,
    and every layer's of a model without experts."""
    dt = cfg.dtype
    with jax.named_scope("dense_mlp"):
        return _linear(jax.nn.silu(_linear(h2, lp["w_gate"], None, dt))
                       * _linear(h2, lp["w_in"], None, dt),
                       lp["w_out"], None, dt)


def _router_logits(rows, lp):
    """The router's float32 logits [N, experts] of ``rows`` [N, H]."""
    return rows.astype(jnp.float32) @ lp["router_wg"].astype(jnp.float32)


def moe_ffn(cfg, h2, lp, valid=None, max_rows=None, router_logits=None,
            period=None):
    """The sparse FFN on its normed input [B, T, H]: the held experts'
    part of the top-k sum plus the shared expert. The experts are gated
    ones (``moe_activation`` "silu", "reglu") or, with "relu2",
    ``down(relu(up)²)``, and so is the shared expert; with
    ``moe_latent_size`` the routed ones run between ``latent_w_in`` and
    ``latent_w_out`` (the second on the held experts' partial sum), the
    router and the shared expert on the full width. ``valid`` [B, T]:
    padding reaches no expert; ``max_rows`` bounds the valid rows.
    ``router_logits`` [B * T, experts]: the router has read elsewhere
    (``moe_router_input`` "layer": ``run_period``) and its matmul is not
    made here. ``period``: ``lp``'s routed experts are the stacks of all
    periods and the grouped matmul indexes this one's
    (``expert_stacks``). Returns (y, aux_loss)."""
    B, T, H = h2.shape
    dt = cfg.dtype
    rows = h2.reshape(B * T, H)
    flat_valid = None if valid is None else valid.reshape(B * T)
    logits = router_logits
    if logits is None:
        with jax.named_scope("router"):
            logits = _router_logits(rows, lp)
    routed = rows
    if cfg.moe_latent_size:
        with jax.named_scope("latent_proj"):
            routed = _linear(rows, lp["latent_w_in"], None, dt)
    with jax.named_scope("experts"):
        y, l_aux = dropless_moe_mlp(
            routed, logits, lp["w_in"], lp["w_out"], lp.get("w_gate"),
            activation=cfg.moe_activation, dtype=dt, top_k=cfg.moe_top_k,
            renormalize=cfg.moe_norm_topk, held=cfg.moe_held_experts,
            valid=flat_valid, max_rows=max_rows,
            score_func=cfg.moe_score_func, select_bias=lp.get("router_b"),
            route_scale=cfg.moe_route_scale, period=period)
    if cfg.moe_latent_size:
        with jax.named_scope("latent_proj"):
            y = _linear(y, lp["latent_w_out"], None, dt)
    if cfg.moe_shared_intermediate_size:
        with jax.named_scope("shared_expert"):
            if cfg.moe_activation == "relu2":
                s = jnp.square(jax.nn.relu(
                    _linear(rows, lp["shared_w_in"], None, dt)))
            else:
                s = GATED[cfg.moe_activation](
                    _linear(rows, lp["shared_w_gate"], None, dt)) \
                    * _linear(rows, lp["shared_w_in"], None, dt)
            s = _linear(s, lp["shared_w_out"], None, dt)
            if cfg.moe_shared_gate:
                gate = jax.nn.sigmoid(_linear(rows, lp["shared_gate_w"],
                                              None, dt).astype(jnp.float32))
                s = s * gate.astype(dt)
            y = y + s
    return y.reshape(B, T, H), l_aux
