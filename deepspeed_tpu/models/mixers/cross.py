"""``"cross"``: attention with queries of its own over **another layer's**
K and V — a decoder-hybrid-decoder's cross-decoder (YOCO; SambaY): the
layer has ``wq`` and ``wo`` (with the biases and, under ``diff_attn``, the
differential form's leaves of an attention layer, ``attention.py``) and no
K/V weights. What it attends is the K/V of the model's latest
whole-context attention layer (``SHARES``) in an earlier run of layers.

In serving it **reads that layer's pool rows and writes none**: its layer
group and its block table are the whole-context group's, ``layer`` is the
writer's place in that pool, and the pool holds one layer for the writer
and all its readers (``cfg.kv_groups`` counts what is written). Without a
cache it reads the pair the writer handed on (``Fwd.carry["kv"]``). No
position term: the kind is refused where ``rope_kinds`` would rotate it.

Scopes (docs/OBSERVABILITY.md): ``cross_attn`` round the layer's ``qkv``
(the queries alone), ``attend`` and ``attn_out``."""

from __future__ import annotations

import jax

from ...parallel.sharding import spec
from ..transformer import _attention, _linear
from . import attention
from .base import Mixer, held

KIND = "cross"
#: the kind whose K/V the layer reads
SHARES = "full"
scope = jax.named_scope


def check(cfg):
    if cfg.rope_kinds is None or KIND in cfg.rope_kinds:
        raise ValueError(
            "\"cross\" layers carry no position term: leave the kind out "
            "of rope_kinds")


def init(cfg, w, gain):
    h, width = cfg.hidden_size, cfg.num_heads * cfg.head_dim
    lp = dict(wq=w((h, width)), wo=w((width, h), w.out_std))
    lp.update(attention.init_biases(cfg, w, ("wq", "wo")))
    if cfg.diff_attn:
        lp.update(attention.init_diff(cfg, w, gain))
    return lp


def specs(cfg):
    return dict(wq=spec("layers", "embed", "heads"),
                wo=spec("layers", "heads", "embed"),
                **attention.spec_extras(cfg, ("wq", "wo")))


def queries(cfg, h1, lp, hold=lambda name, y: y):
    """The layer's queries [B, T, heads, D] (under ``diff_attn`` as the
    attention takes joined pairs). ``hold``: ``attention.full_qkv``'s."""
    B, T, _ = h1.shape
    q = hold("q", _linear(h1, lp["wq"], lp.get("wq_b"), cfg.dtype)).reshape(
        B, T, cfg.num_heads, cfg.head_dim)
    return attention.diff_queries(q) if cfg.diff_attn else q


def reference(cfg, fwd):
    def mixer(h1, lp, i):
        with scope("qkv"):
            q = queries(cfg, h1, lp)
        with scope("attend"):
            k, v = fwd.carry["kv"]
            attn = _attention(q, k, v, cfg, causal=True)
        with scope("attn_out"):
            return attention.full_out(cfg, attn, None, lp,
                                      fwd.depth(KIND, i))
    return mixer


def paged(cfg, fwd):
    pools, table = fwd.pools, fwd.tables[fwd.group_of[KIND]]
    k_name, v_name = fwd.leaf(KIND, "k"), fwd.leaf(KIND, "v")
    # the writer's place among its group's layers: the latest layer of
    # its kind before this run
    layer = fwd.layer(SHARES, -1)

    def mixer(h1, lp, i):
        with scope("qkv"):
            q = queries(cfg, h1, lp, held(cfg))
        with scope("attend"):
            attn = fwd.attend(q, {"k": pools[k_name], "v": pools[v_name]},
                              layer, table, fwd.start_pos, fwd.n_tokens,
                              None, window=0)
        with scope("attn_out"):
            return attention.full_out(cfg, attn, None, lp,
                                      fwd.depth(KIND, i))
    return mixer


def count(cfg, staged, bucket_chunk: int, block_size: int):
    """``shared_kv_read_tokens``: the K/V positions the layers' walks of
    the writer's pool rows read in this forward, every ``cross`` layer's:
    a row's whole context, once a layer."""
    return {"shared_kv_read_tokens": cfg.layers_of(KIND) * sum(
        seq.seen_tokens + len(toks) for seq, toks in staged)}


CROSS = Mixer(init=init, specs=specs, reference=reference, paged=paged,
              scope="cross_attn", check=check, paged_walk=True,
              totals=("shared_kv_read_tokens",),
              record=("shared_kv_read_tokens",), count=count,
              takes=("kv",), shares=SHARES, holds=True)
