"""Ragged sequence state: descriptors, block tables, paged KV cache.

Counterpart of reference ``inference/v2/ragged/ragged_manager.py``
(``DSStateManager``), ``sequence_descriptor.py`` (``DSSequenceDescriptor``)
and ``kv_cache.py`` (``BlockedKVCache``): tracks per-sequence seen-token
counts and KV block ownership, allocates blocks on demand, and owns the
device-side paged cache tensors [L, num_blocks, KH, block_size, D] (the
per-(block, kv-head) slab is the trailing [block_size, D] — the layout the
Pallas paged-attention index maps depend on, ops/paged_attention.py).
A group carries its own pool's layout (``KVGroup.leaves``,
``block_shapes``: ``TransformerConfig.kv_layouts``): latent attention's
pool is a leaf ``kv`` [L, num_blocks, block_size, W], a token's ``(c,
k_r)`` row with no head axis (ops/latent_attention.py), at the width of
the group's own kind; a group with sparse layers has a second leaf of
another width beside it, ``ki`` [L, num_blocks, block_size, index dim],
a block of which belongs to whoever holds the same block of ``kv``.
Whatever moves whole blocks of a group — the allocator, the prefix
cache, export / import, the preemption stash, a trim, the release behind
a window — reads the leaves' second axis and nothing behind it.

KV by layer group (docs/SERVING.md "The pool contract"): layers whose K/V
has one lifetime — the whole context, or the last ``window`` positions —
are a group (``TransformerConfig.kv_groups``) with a pool, an allocator
and a block table a sequence of their own. The tables are indexed alike,
by the position's block; a window group's blocks that lie wholly behind
every position a later query can attend go back to their free list after
the put that passes them (``release_behind``), their table entries turn
-1, and neither the write plan nor the kernel's walk, which starts at the
window's first live block, reads an entry behind it. The first group is
the one whose K/V lives longest: ``num_blocks`` is its pool's size, and
``free_blocks`` / ``available_blocks`` / the reservation ledger count its
blocks; a further group is sized so that it is never the one that runs
out (``group_blocks``) and is checked all the same. What assumes the
whole context resident in one pool works on the first group of a model
that has one group, and raises ``ReleasedKVUnsupported`` otherwise.

Prefix cache (docs/SERVING.md "Prefix caching"): every *full* KV block a
sequence fills is registered in a hash index keyed by the chain hash of
its token content — ``h_i = hash((h_{i-1}, tokens_i))`` — so a later
sequence whose prompt starts with the same tokens at the same positions
shares those device blocks instead of re-prefilling them
(:meth:`DSStateManager.match_prefix`). Shared blocks are immutable: a
sequence only ever writes KV at positions ≥ its matched length, which land
in blocks it allocated itself; the last, partially-filled block of a
prompt is never matched (the walk stops at the last full-block boundary
strictly below ``len(prompt)``), so the tail is re-prefilled — the
copy-on-write of this design. The cache holds one reference of its own on
each indexed block; blocks whose only reference is the cache's are
*unreferenced* and evicted in LRU order when ``allocate`` would otherwise
fail (or when ``max_cached_blocks`` is exceeded).
"""

from __future__ import annotations

import time
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .blocked_allocator import BlockedAllocator


@dataclass
class DSSequenceDescriptor:
    uid: int
    seen_tokens: int = 0                   # tokens already in the KV cache
    kv_blocks: List[int] = field(default_factory=list)
    input_tokens: List[int] = field(default_factory=list)  # pending prompt
    # prefix-cache chain state: hash through the last full block, how many
    # leading blocks have been hashed, and the tokens of the partial block
    chain_hash: int = 0
    hashed_blocks: int = 0
    pending_tokens: List[int] = field(default_factory=list)
    # a hybrid model's recurrent layers: this sequence's slot in the
    # state tree (-1: the model has none)
    state_slot: int = -1
    # this sequence's slot in the engine's next-token buffer, kept while
    # it is tracked: the forward writes the id it draws for the row there
    # and a later one-token row may take its token from it
    # (``InferenceEngineV2.next_ids``)
    id_slot: int = -1
    # K/V by layer group: ``kv_blocks`` is the first group's table, these
    # are the further groups'; every table is indexed by the position's
    # block and as long as the context, and a block a window group has
    # handed back is -1 in it. ``released[g]``: group g's leading blocks
    # handed back so far
    more_blocks: List[List[int]] = field(default_factory=list)
    released: List[int] = field(default_factory=lambda: [0])
    # the tables once more as one int32 array [groups, blocks], what a
    # forward's staging copies from (``DSStateManager.table_rows`` keeps
    # it: ``rows_synced[g]`` entries of group g's table are in it,
    # ``rows_cleared[g]`` leading ones are marked handed back)
    rows: Optional[np.ndarray] = None
    rows_synced: List[int] = field(default_factory=list)
    rows_cleared: List[int] = field(default_factory=list)

    @property
    def cur_allocated_blocks(self) -> int:
        return len(self.kv_blocks)

    @property
    def tables(self) -> List[List[int]]:
        """The block table of each layer group, the first group's first."""
        return [self.kv_blocks] + self.more_blocks


@dataclass
class KVGroup:
    """Layers whose K/V has one lifetime: ``window`` positions (0: the
    whole context), their pool's layout — the leaves ``<leaf><suffix>``,
    each ``[layers, blocks, *its block shape]`` — and its allocator."""
    window: int
    layers: int
    allocator: BlockedAllocator
    suffix: str = ""
    leaves: Tuple[str, ...] = ("k", "v")
    block_shapes: Tuple[Tuple[int, ...], ...] = ()

    @property
    def names(self) -> List[str]:
        return [leaf + self.suffix for leaf in self.leaves]

    @property
    def block_shape(self) -> Tuple[int, ...]:
        """The first leaf's block (every leaf's, but for an index leaf)."""
        return self.block_shapes[0]

    @property
    def pool_shape(self) -> Tuple[int, ...]:
        return self.pool_shapes[self.names[0]]

    @property
    def pool_shapes(self) -> Dict[str, Tuple[int, ...]]:
        lead = (self.layers, self.allocator.total_blocks)
        return {name: lead + shape
                for name, shape in zip(self.names, self.block_shapes)}


class DSStateManager:
    """Sequence registry + paged KV cache (reference ragged_manager.py:204)."""

    def __init__(self, model_cfg, max_tracked_sequences: int = 256,
                 num_blocks: int = 256, block_size: int = 16,
                 dtype=None, sharding=None,
                 enable_prefix_cache: bool = False,
                 prefix_cache_max_blocks: Optional[int] = None,
                 kv_quant: bool = False, kv_quant_dtype: str = "int8",
                 scale_sharding=None,
                 kv_tier_enabled: bool = False,
                 kv_tier_host_bytes: int = 64 * 1024 * 1024,
                 kv_tier_disk_path: Optional[str] = None,
                 kv_tier_disk_bytes: int = 0,
                 state_slots: int = 0,
                 group_blocks: Optional[Sequence[int]] = None):
        from ..kv_quant import kv_bytes_per_block

        self.cfg = model_cfg
        # (window, layers) of each layer group; ``group_blocks``: the
        # further groups' pool sizes (None: as large as the first)
        kv_groups = (model_cfg.kv_groups() if hasattr(model_cfg, "kv_groups")
                     else ((0, model_cfg.num_layers),))
        sizes = [num_blocks] + [min(int(n), num_blocks) for n in (
            group_blocks or [num_blocks] * (len(kv_groups) - 1))]
        if len(sizes) != len(kv_groups):
            raise ValueError(f"{len(kv_groups)} layer groups, "
                             f"{len(sizes)} pool sizes")
        # A hybrid model's recurrent layers keep a fixed-size state a
        # sequence (models/hybrid.state_shapes) beside the paged K/V of
        # its attention layers: ``state_slots`` slots, one a tracked
        # sequence, taken with the sequence and given back by flush.
        self.recurrent = getattr(model_cfg, "num_linear_layers", 0) > 0
        self.state_slots = int(state_slots) if self.recurrent else 0
        if self.recurrent and self.state_slots <= 0:
            raise ValueError("a model with recurrent layers needs "
                             "state_slots > 0")
        if self.recurrent and enable_prefix_cache:
            self.refuse_recurrent("the prefix cache")
        self.block_size = block_size
        self.num_blocks = num_blocks
        self.max_tracked_sequences = max_tracked_sequences
        # quantized KV (docs/SERVING.md "KV quantization"): pools stored
        # as symmetric int8 or float8_e4m3fn (``kv_quant_dtype``) with
        # per-(layer, block, kv-head) f32 scale planes — half the HBM
        # bytes per block vs bf16, so a fixed byte budget buys ~2x the
        # blocks (inference/v2/kv_quant.py)
        self.kv_quant = bool(kv_quant)
        self.kv_quant_dtype = str(kv_quant_dtype)
        per_layer = kv_bytes_per_block(model_cfg, block_size, self.kv_quant,
                                       dtype) // sum(n for _, n in kv_groups)
        if hasattr(model_cfg, "kv_layouts"):
            layouts = model_cfg.kv_layouts(block_size)
        else:
            block = (model_cfg.kv_heads, block_size, model_cfg.head_dim)
            layouts = ({"k": block, "v": block},) * len(kv_groups)
        # a pool with no kv-head axis (latent attention's): each group's
        # block costs what its own leaves hold (their widths differ)
        self.headless = "k" not in layouts[0]
        itemsize = jnp.dtype(dtype or model_cfg.dtype).itemsize

        def block_bytes(layout, layers):
            if not self.headless:
                # k and v, and a leaf beside them (a block-sparse
                # layer's compressed keys) at its own block shape
                return per_layer * layers + layers * itemsize * sum(
                    int(np.prod(shape)) for name, shape in layout.items()
                    if name not in ("k", "v"))
            return layers * itemsize * sum(
                int(np.prod(shape)) for shape in layout.values())

        self.groups = [
            KVGroup(window, layers,
                    BlockedAllocator(size, bytes_per_block=block_bytes(
                        layout, layers)),
                    "" if g == 0 else str(g), tuple(layout),
                    tuple(layout.values()))
            for g, ((window, layers), size, layout) in enumerate(zip(
                kv_groups, sizes, layouts))]
        if self.headless and (kv_quant or kv_tier_enabled
                              or sharding is not None):
            self.refuse_latent("quantized pools, the KV tier and a pool "
                               "sharded by head")
        # compressed keys beside k / v (``"kc"``): a block's last row is a
        # kernel that ends in the next block, so a block is its own
        # sequence's; scales and splits by head know no such leaf
        self.compressed = "kc" in layouts[0]
        if self.compressed and (enable_prefix_cache or kv_quant
                                or kv_tier_enabled or sharding is not None):
            self.refuse_compressed("the prefix cache, quantized pools, "
                                   "the KV tier and a pool sharded by head")
        self.allocator = self.groups[0].allocator
        # blocks handed back behind a window since this manager was built
        self.blocks_released = 0
        if len(self.groups) > 1 and (enable_prefix_cache or kv_quant
                                     or kv_tier_enabled):
            self.refuse_grouped("the prefix cache, the KV tier and "
                                "quantized pools")
        self._seqs: Dict[int, DSSequenceDescriptor] = {}
        # -- reservation ledger (docs/SERVING.md "Admission and
        # preemption"): per-sequence TOTAL projected block need, recorded
        # at admission. The scheduler's reservation admission keeps
        # ``sum(unfilled) <= available_blocks`` — every admitted sequence
        # can always obtain the blocks it still needs, so chunk-by-chunk
        # prefill can never wedge the pool. Passive when nobody reserves
        # (the ledger is empty → headroom == available_blocks).
        self._reserved: Dict[int, int] = {}
        # -- preemption spill store: whole-sequence KV exports parked
        # under pressure. Slab bytes live in the KV tier when one is
        # configured (byte-bounded LRU + disk demotion + CRC — dropped
        # entries degrade to a lossless greedy re-prefill), else in a
        # plain host-RAM dict bounded by the parked-sequence count.
        self._preempt_store: Dict[int, dict] = {}
        # -- prefix cache ---------------------------------------------------
        self.prefix_cache_enabled = bool(enable_prefix_cache)
        self.prefix_cache_max_blocks = (prefix_cache_max_blocks
                                        if prefix_cache_max_blocks else 0)
        # index key = (parent_chain_hash, block_tokens_tuple): the block's
        # own tokens are compared EXACTLY on lookup (dict equality), so a
        # builtin-hash collision cannot alias two different blocks; only
        # the parent linkage is compressed to its 64-bit chain hash.
        self._index: "OrderedDict[tuple, int]" = OrderedDict()  # key -> block
        self._block_hash: Dict[int, tuple] = {}                 # block -> key
        self._evictable = 0       # indexed blocks whose only ref is the
        #                           cache's own (kept incrementally — the
        #                           admission path reads it per candidate)
        self._stats = {"hits": 0, "misses": 0, "evictions": 0,
                       "tokens_saved": 0, "queries": 0}
        # tiered KV memory (docs/SERVING.md "KV tiering"): host-RAM/disk
        # spillover for evicted prefix-cache blocks with restore on
        # match. None = the historical drop-on-evict path byte for byte.
        self._tier = None
        self._restore_times: List[float] = []   # drained by the serving
        #                                         layer into kv_tier_restore_s
        if kv_tier_enabled:
            self.configure_kv_tier(True, host_bytes=kv_tier_host_bytes,
                                   disk_path=kv_tier_disk_path,
                                   disk_bytes=kv_tier_disk_bytes)
        dt = dtype or model_cfg.dtype
        # [L, NB, KH, bs, D]: the per-(block, kv-head) slab is the trailing
        # [bs, D] — one tileable VMEM block, DMA'd directly by the Pallas
        # paged-attention index maps (ops/paged_attention.py).
        # ``sharding``: optional NamedSharding placing KH over the tensor
        # axis (TP serving — reference v2 sharding/qkv.py:166 head split).
        shape = self.groups[0].pool_shape
        from ..kv_quant import pool_dtype as _pool_dtype

        pool_dt = _pool_dtype(self.kv_quant_dtype) if self.kv_quant else dt

        def _alloc(shp, adt, shard):
            if shard is None:
                return jnp.zeros(shp, adt)
            # allocate each device's shard directly — a full pool on one
            # device before resharding could OOM exactly when TP matters
            return jax.jit(lambda: jnp.zeros(shp, adt),
                           out_shardings=shard)()

        # one buffer per leaf: the forward donates the cache and writes it
        # in place (paged_model.py), and one buffer cannot be donated twice
        self.kv_cache = {
            name: _alloc(shape, pool_dt, sharding) for group in self.groups
            for name, shape in group.pool_shapes.items()}
        if self.kv_quant:
            # symmetric per-(layer, block, kv-head) scales, indexed by
            # pool block id — a prefix-shared block shares its scale for
            # free; freed blocks' stale entries are ignored (not reset) by
            # the fresh-block write rule in kv_quant.quantized_block_write
            sshape = shape[:3]
            self.kv_cache["k_scale"] = _alloc(sshape, jnp.float32,
                                              scale_sharding)
            self.kv_cache["v_scale"] = _alloc(sshape, jnp.float32,
                                              scale_sharding)
        # the recurrent state tree: [L_linear, slots + 1, ...] a leaf —
        # the last slot is scratch, where a padded batch row's (unchanged)
        # state is written. Donated to the forward with the pool. A slot
        # is not cleared when it is given back: a row at start_pos 0
        # starts from zero whatever its slot holds (paged_model.py).
        self.state_cache: Dict[str, jax.Array] = {}
        self._free_slots: List[int] = list(range(self.state_slots))[::-1]
        # next-token slots, one a tracked sequence (any model); the slot
        # behind the last is scratch, a padded batch row's
        self.id_slots = int(max_tracked_sequences)
        self._free_id_slots: List[int] = list(range(self.id_slots))[::-1]
        if self.recurrent:
            from ....models.hybrid import state_shapes

            self.state_cache = {
                name: jnp.zeros(shp, adt) for name, (shp, adt)
                in state_shapes(model_cfg, self.state_slots + 1).items()}

    def refuse_recurrent(self, what: str) -> None:
        """The typed refusal of a feature that assumes per-token KV."""
        from ....models.hybrid import RecurrentStateUnsupported

        raise RecurrentStateUnsupported(
            f"{what} needs per-token KV in every layer; this model keeps "
            f"a recurrent state in {self.cfg.num_linear_layers} of its "
            f"{self.cfg.num_layers} layers, which cannot be cut at a "
            "token or shared by prefix (snapshots of state are not "
            "built yet)")

    def refuse_compressed(self, what: str) -> None:
        """The typed refusal of a feature that assumes a block holds its
        own tokens' K/V and nothing else."""
        from ....models.hybrid import CompressedKeysUnsupported

        raise CompressedKeysUnsupported(
            f"{what} assume(s) that a pool block holds its own tokens' "
            "K/V only; a block-sparse layer keeps compressed keys beside "
            "them (leaf kc), the last of which a block's successor "
            "completes: a block is not shared, scaled or split by head")

    def refuse_latent(self, what: str) -> None:
        """The typed refusal of a feature that assumes K/V by kv-head."""
        from ....models.hybrid import LatentKVUnsupported

        raise LatentKVUnsupported(
            f"{what} assume(s) a pool [L, NB, KH, bs, D] with a kv-head "
            "axis (a scale a head, a split by head); this model's cache "
            "is one latent row a token, shared by every head "
            f"({[g.block_shapes for g in self.groups]} a block)")

    def refuse_grouped(self, what: str) -> None:
        """The typed refusal of a feature that assumes a sequence's whole
        context resident in one pool."""
        from ....models.hybrid import ReleasedKVUnsupported

        raise ReleasedKVUnsupported(
            f"{what} assume(s) a sequence's whole context resident in one "
            f"pool; this model keeps K/V in {len(self.groups)} layer "
            f"group(s) (windows {[g.window for g in self.groups]}; 0 = the "
            "whole context), and a window group hands the blocks behind "
            "its window back while the sequence lives")

    # -- the forward's cache ------------------------------------------------
    @property
    def forward_cache(self) -> Dict[str, jax.Array]:
        """What the paged forward is given and donated: the K/V pool and,
        for a hybrid model, the recurrent state tree beside it."""
        return {**self.kv_cache, **self.state_cache}

    @forward_cache.setter
    def forward_cache(self, cache: Dict[str, jax.Array]) -> None:
        self.state_cache = {k: cache[k] for k in self.state_cache}
        self.kv_cache = {k: v for k, v in cache.items()
                         if k not in self.state_cache}

    @property
    def free_state_slots(self) -> int:
        return len(self._free_slots)

    # -- sequence registry -------------------------------------------------
    def get_or_create_sequence(self, uid: int) -> DSSequenceDescriptor:
        if uid not in self._seqs:
            if len(self._seqs) >= self.max_tracked_sequences:
                raise RuntimeError("max tracked sequences exceeded")
            seq = DSSequenceDescriptor(
                uid=uid, more_blocks=[[] for _ in self.groups[1:]],
                released=[0] * len(self.groups))
            if self.recurrent:
                if not self._free_slots:
                    raise RuntimeError("no free recurrent-state slot")
                seq.state_slot = self._free_slots.pop()
            seq.id_slot = self._free_id_slots.pop()
            self._seqs[uid] = seq
        return self._seqs[uid]

    def get_sequence(self, uid: int) -> Optional[DSSequenceDescriptor]:
        return self._seqs.get(uid)

    def flush_sequence(self, uid: int) -> None:
        """Release a finished sequence's blocks (reference engine_v2.flush).
        Blocks held by the prefix cache stay resident (the cache's own
        reference keeps them) and become evictable once no sequence refers
        to them."""
        seq = self._seqs.pop(uid, None)
        self._reserved.pop(uid, None)     # reservation dies with the state
        if seq is not None:
            for g, table in enumerate(seq.tables):
                live = [b for b in table if b >= 0]
                if live:
                    self._release_blocks(live, g)
        if seq is not None:
            self._give_slots(seq)

    def _give_slots(self, seq: DSSequenceDescriptor) -> None:
        """The slots of a sequence that is no longer tracked."""
        if seq.state_slot >= 0:
            self._free_slots.append(seq.state_slot)
        self._free_id_slots.append(seq.id_slot)

    def _release_blocks(self, blocks: List[int], group: int = 0) -> None:
        """Drop one reference per block and keep the incremental
        evictable count honest: an indexed block whose only remaining
        reference is the cache's own just became reclaimable. The single
        home for this transition — flush, trim and the release behind a
        window all go through it."""
        self.groups[group].allocator.release(blocks)
        if self.prefix_cache_enabled and group == 0:
            for b in blocks:
                if (b in self._block_hash
                        and self.allocator.ref_count(b) == 1):
                    self._evictable += 1

    def first_live_block(self, window: int, position: int) -> int:
        """The first block a query at ``position`` (and any later one)
        can attend under ``window``: the paged kernel's walk starts
        there, and every block before it is dead."""
        if not window:
            return 0
        return max(position - window + 1, 0) // self.block_size

    def table_rows(self, seq: DSSequenceDescriptor) -> np.ndarray:
        """The sequence's block tables as one int32 array ``[groups,
        blocks]``, for the forward's staging: a decode step of a 40k-token
        context would otherwise turn a list of 700 ints into an array a
        group a step. The array is kept beside the lists and brought up
        to date here by what changed since the last call — tables grow at
        the end (allocation, a matched prefix, an import), lose entries
        at the end (``trim_sequence``, which lowers the mark) and are
        handed back from the front (``release_behind``)."""
        tables = seq.tables
        n = len(tables[0])
        if seq.rows is None:
            width = -(-self.cfg.max_seq_len // self.block_size)
            seq.rows = np.full((len(tables), max(width, n)), -1, np.int32)
            seq.rows_synced = [0] * len(tables)
            seq.rows_cleared = [0] * len(tables)
        for g, table in enumerate(tables):
            have = seq.rows_synced[g]
            if n > have:
                seq.rows[g, have:n] = table[have:]
            elif n < have:
                seq.rows[g, n:have] = -1
            seq.rows_synced[g] = n
            gone = seq.released[g]
            if gone > seq.rows_cleared[g]:
                seq.rows[g, seq.rows_cleared[g]:gone] = -1
                seq.rows_cleared[g] = gone
        return seq.rows[:, :n]

    def release_behind(self, seq: DSSequenceDescriptor) -> int:
        """Hand back every block of a window group that lies wholly
        behind the window of the sequence's next query (position
        ``seen_tokens``): it belongs to no later query. A shared block
        loses this sequence's reference only. Called after the put that
        passed them was dispatched and its tokens were recorded. Returns
        the number of blocks handed back."""
        handed = 0
        for g, (group, table) in enumerate(zip(self.groups, seq.tables)):
            first = min(self.first_live_block(group.window, seq.seen_tokens),
                        len(table))
            done = seq.released[g]
            if first > done:
                self._release_blocks([b for b in table[done:first] if b >= 0],
                                     g)
                table[done:first] = [-1] * (first - done)
                seq.released[g] = first
                handed += first - done
        self.blocks_released += handed
        return handed

    def trim_sequence(self, uid: int, n_tokens: int) -> int:
        """KV rollback: drop the trailing ``n_tokens`` from a sequence —
        the speculative-decoding rejection path (spec/: drafts the target
        model refuted must vanish from the cache before the next step).

        Trailing blocks that become empty are ``release``d through the
        refcount machinery: a private block returns to the free list; a
        block the prefix cache also holds stays resident (the cache's own
        reference keeps it) and becomes evictable. Blocks *below* the new
        length — including prefix-shared ones — are untouched: no refcount
        changes, no index changes.

        Interaction with the prefix-cache index: draft tokens are never
        chain-registered (the scheduler defers ``record_tokens`` until
        after verification — ``put(defer_commit=True)``), so a trim of
        speculative tokens can never cut into hashed coverage. Trimming
        *into* an already-indexed block is refused with ``ValueError``:
        the retained prefix of such a block would later be overwritten in
        place while the index (and possibly other sequences) still
        reference the old content. Callers that need that must flush and
        re-prefill instead.

        Returns the number of blocks released.
        """
        seq = self._seqs.get(uid)
        if seq is None or n_tokens <= 0:
            return 0
        if self.recurrent:
            self.refuse_recurrent("trim_sequence (speculative rollback)")
        if len(self.groups) > 1:
            self.refuse_grouped("trim_sequence (speculative rollback)")
        if n_tokens > seq.seen_tokens:
            raise ValueError(
                f"cannot trim {n_tokens} tokens from sequence {uid} "
                f"({seq.seen_tokens} seen)")
        new_seen = seq.seen_tokens - n_tokens
        if seq.released[0] > self.first_live_block(self.groups[0].window,
                                                   new_seen):
            self.refuse_grouped(
                f"trim_sequence of {n_tokens} tokens (a rollback past a "
                f"block sequence {uid} has handed back)")
        if new_seen < seq.hashed_blocks * self.block_size:
            raise ValueError(
                f"cannot trim sequence {uid} into prefix-indexed blocks "
                f"({seq.hashed_blocks} blocks hashed, want "
                f"{new_seen} tokens)")
        keep = -(-new_seen // self.block_size)       # ceil; 0 when new_seen=0
        dropped = seq.kv_blocks[keep:]
        # sharing happens only through the prefix index, and indexed
        # blocks sit inside hashed coverage (guarded above) — a dropped
        # block that is shared yet unindexed means some other sequence
        # reads KV this trim is rolling back: corruption, refuse loudly
        for b in dropped:
            if self.allocator.is_shared(b) and b not in self._block_hash:
                raise ValueError(
                    f"cannot trim block {b} of sequence {uid}: shared "
                    "outside the prefix index (sharing invariant violated)")
        del seq.kv_blocks[keep:]
        if seq.rows is not None:    # what grows back is other blocks
            seq.rows_synced[0] = min(seq.rows_synced[0], keep)
        seq.seen_tokens = new_seen
        # chain state: un-blocked pending tokens past the new end are gone
        over = (seq.hashed_blocks * self.block_size
                + len(seq.pending_tokens)) - new_seen
        if over > 0:
            del seq.pending_tokens[len(seq.pending_tokens) - over:]
        if dropped:
            self._release_blocks(dropped)
        return len(dropped)

    # -- KV handoff (disaggregated prefill/decode) --------------------------
    def export_sequence(self, uid: int,
                        chunk_blocks: int = 0) -> Optional[Dict[str, object]]:
        """Host-RAM snapshot of a sequence's KV state for cross-engine
        handoff (docs/SERVING.md "Disaggregated serving"): every pool
        slab the sequence's block table references — K and V, plus the
        ``k_scale``/``v_scale`` planes under kv_quant — copied
        device→host (async transfer started for all slabs before any is
        materialized, so the copies overlap), with the metadata
        :meth:`import_sequence` validates against. Whole blocks are
        copied verbatim (stale slots past ``seen_tokens`` included), so
        an import reproduces the pool content byte-for-byte — attention
        masks those positions on both sides. Shared prefix blocks export
        like private ones (content copy; the source's refcounts are
        untouched). Returns ``None`` for unknown/empty sequences. The
        source sequence keeps its state — the caller flushes after the
        payload is staged.

        ``chunk_blocks`` > 0 switches to the block-granularity streamed
        form (docs/SERVING.md "Multi-host serving"): the payload carries
        ``"chunks"`` — a list of per-chunk slab dicts covering at most
        ``chunk_blocks`` blocks each. Every chunk's device→host copy is
        dispatched BEFORE any chunk materializes (so the copies
        overlap), and the payload holds host numpy arrays — staged
        payloads pin host RAM only, never device HBM — in units a
        consumer (the wire codec, the import scatter) can stream one at
        a time, overlapping a long-context handoff's transfer with
        ongoing decode. Byte content is identical to the whole-slab
        form (tests assert)."""
        seq = self._seqs.get(uid)
        if seq is None or not seq.kv_blocks:
            return None
        if len(self.groups) > 1 or seq.released[0]:
            self.refuse_grouped("export_sequence (KV handoff, the "
                                "preemption stash)")
        meta = {"seen_tokens": seq.seen_tokens,
                "block_size": self.block_size,
                "kv_quant": self.kv_quant,
                "kv_quant_dtype": self.kv_quant_dtype,
                "n_blocks": len(seq.kv_blocks)}
        if self.recurrent:
            # the recurrent layers' state goes with the blocks, whole
            meta["state"] = {name: np.asarray(leaf[:, seq.state_slot])
                             for name, leaf in self.state_cache.items()}
        if chunk_blocks and chunk_blocks > 0:
            device_chunks = []
            for s in range(0, len(seq.kv_blocks), int(chunk_blocks)):
                ids = jnp.asarray(seq.kv_blocks[s:s + int(chunk_blocks)],
                                  dtype=jnp.int32)
                arrs = {name: jnp.take(pool, ids, axis=1)
                        for name, pool in self.kv_cache.items()}
                for a in arrs.values():
                    try:
                        a.copy_to_host_async()
                    except Exception:   # backend without async host copy
                        pass
                device_chunks.append(arrs)
            # materialize AFTER every copy was dispatched (each asarray
            # waits only for its own chunk's transfer) — the device
            # buffers are released here, so a staged payload pins host
            # RAM, not HBM
            meta["chunk_blocks"] = int(chunk_blocks)
            meta["chunks"] = [{name: np.asarray(a)
                               for name, a in c.items()}
                              for c in device_chunks]
            return meta
        ids = jnp.asarray(seq.kv_blocks, dtype=jnp.int32)
        arrs = {name: jnp.take(pool, ids, axis=1)
                for name, pool in self.kv_cache.items()}
        for a in arrs.values():
            try:
                a.copy_to_host_async()
            except Exception:   # backend without async host copy
                pass
        meta["slabs"] = {name: np.asarray(a) for name, a in arrs.items()}
        return meta

    def import_sequence(self, uid: int, payload: Dict[str, object],
                        tokens: Sequence[int]) -> None:
        """Adopt an exported sequence: allocate fresh blocks, scatter the
        payload's slabs (and scale planes) into this pool at the new
        ids, and seed the descriptor at the source's ``seen_tokens`` —
        the destination decodes from here exactly as the source would
        have (byte-lossless: int8/f32/bf16 slabs round-trip host copies
        exactly).

        ``tokens`` are the actual tokens the imported KV encodes (length
        must equal ``seen_tokens``): they replay ``record_tokens`` so
        the destination's prefix-cache hash chain covers the imported
        blocks — full blocks register in the index and later prompts
        sharing the prefix hit, exactly as if the prefill had run here.

        Raises on representation mismatch (block size / kv_quant — a
        heterogeneous fleet must recompute instead), on a uid that
        already has state, and on insufficient capacity (after LRU
        prefix-cache eviction). Failure leaves the manager untouched —
        the caller falls back to re-prefilling.

        Accepts BOTH payload forms: whole-slab (``"slabs"``) and the
        block-granularity streamed form (``"chunks"`` — see
        :meth:`export_sequence`); chunked payloads scatter one chunk at
        a time, so the first chunks land while later ones are still
        materializing/arriving."""
        if len(self.groups) > 1:
            self.refuse_grouped("import_sequence (KV handoff)")
        chunks = payload.get("chunks")
        slabs = (payload["slabs"] if chunks is None
                 else {k: None for k in chunks[0]} if chunks
                 else {k: None for k in self.kv_cache})
        if int(payload["block_size"]) != self.block_size:
            raise ValueError(
                f"KV import block_size mismatch: payload "
                f"{payload['block_size']} vs pool {self.block_size}")
        if bool(payload["kv_quant"]) != self.kv_quant:
            raise ValueError(
                f"KV import representation mismatch: payload kv_quant="
                f"{payload['kv_quant']} vs pool kv_quant={self.kv_quant}")
        # dtype axis of the representation check (int8 vs fp8_e4m3):
        # pre-dtype payloads default to int8, the only representation
        # that existed when they were written
        pay_dt = str(payload.get("kv_quant_dtype", "int8"))
        if self.kv_quant and pay_dt != self.kv_quant_dtype:
            raise ValueError(
                f"KV import representation mismatch: payload "
                f"kv_quant_dtype={pay_dt!r} vs pool "
                f"{self.kv_quant_dtype!r}")
        if set(slabs) != set(self.kv_cache):
            raise ValueError(f"KV import slab keys {sorted(slabs)} != "
                             f"pool keys {sorted(self.kv_cache)}")
        state = payload.get("state") or {}
        if {k: np.shape(v) for k, v in state.items()} != {
                k: v.shape[:1] + v.shape[2:]
                for k, v in self.state_cache.items()}:
            raise ValueError("KV import recurrent-state mismatch: the "
                             "payload and this model do not keep the same "
                             "state a sequence")
        seen = int(payload["seen_tokens"])
        if len(tokens) != seen:
            raise ValueError(f"KV import needs the {seen} tokens the KV "
                             f"encodes, got {len(tokens)}")
        existing = self._seqs.get(uid)
        if existing is not None and (existing.seen_tokens
                                     or existing.kv_blocks):
            raise ValueError(f"cannot import into sequence {uid}: it "
                             "already has KV state")
        n = int(payload["n_blocks"])
        if chunks is not None:
            got = sum(int(np.shape(next(iter(c.values())))[1])
                      for c in chunks)
            if got != n:
                raise ValueError(f"KV import chunks cover {got} blocks, "
                                 f"payload claims {n}")
        short = n - self.allocator.free_blocks
        if short > 0 and self.prefix_cache_enabled:
            self._evict(short)
        if n > self.allocator.free_blocks:
            raise RuntimeError(
                f"cannot import {n} KV blocks "
                f"({self.allocator.free_blocks} free)")
        seq = self.get_or_create_sequence(uid)
        blocks = self.allocator.allocate(n)
        try:
            if self.recurrent:
                self.state_cache = {
                    name: leaf.at[:, seq.state_slot].set(
                        jnp.asarray(state[name], dtype=leaf.dtype))
                    for name, leaf in self.state_cache.items()}
            if chunks is not None:
                # streamed form: glue the chunks per slab and scatter
                # ONCE per pool tensor — a per-chunk `.at[].set` would
                # copy the whole pool per chunk (O(chunks x pool
                # bytes)), the exact long-context case chunking exists
                # to help. The streaming benefit already happened
                # upstream (per-chunk host copies / wire frames).
                ids = jnp.asarray(blocks, dtype=jnp.int32)
                for name, pool in self.kv_cache.items():
                    glued = np.concatenate(
                        [np.asarray(c[name]) for c in chunks], axis=1)
                    self.kv_cache[name] = pool.at[:, ids].set(
                        jnp.asarray(glued, dtype=pool.dtype))
            else:
                ids = jnp.asarray(blocks, dtype=jnp.int32)
                for name, pool in self.kv_cache.items():
                    self.kv_cache[name] = pool.at[:, ids].set(
                        jnp.asarray(slabs[name], dtype=pool.dtype))
            seq.kv_blocks.extend(blocks)
            seq.seen_tokens = seen
            # prefix-index coherence: rebuild the hash chain over the
            # imported tokens (no-op when the cache is disabled)
            self.record_tokens(seq, tokens)
        except Exception:
            self._seqs.pop(uid, None)
            self.allocator.release(blocks)
            self._give_slots(seq)
            raise

    @property
    def tracked_sequences(self) -> List[int]:
        return list(self._seqs)

    @property
    def free_blocks(self) -> int:
        return self.allocator.free_blocks

    # -- block math ---------------------------------------------------------
    def blocks_needed(self, seq: DSSequenceDescriptor, new_tokens: int) -> int:
        total = seq.seen_tokens + new_tokens
        need = -(-total // self.block_size)   # ceil
        return max(0, need - len(seq.kv_blocks))

    def maybe_allocate_kv(self, seq: DSSequenceDescriptor, new_tokens: int):
        need = self.blocks_needed(seq, new_tokens)
        if need > 0:
            short = need - self.allocator.free_blocks
            if short > 0 and self.prefix_cache_enabled:
                self._evict(short)           # LRU unreferenced cached blocks
            # every group's table grows alike; all or nothing
            if self.groups_short(need):
                raise ValueError(f"cannot allocate {need} blocks in every "
                                 "layer group")
            for group, table in zip(self.groups, seq.tables):
                table.extend(group.allocator.allocate(need))

    def groups_short(self, blocks_needed: int) -> bool:
        """Whether a further group's pool cannot give ``blocks_needed``
        blocks (the first group's is ``available_blocks``' to say)."""
        return any(blocks_needed > g.allocator.free_blocks
                   for g in self.groups[1:])

    def resident_bytes(self) -> Dict[str, int]:
        """K/V bytes the pools hold for the tracked sequences, beside the
        bytes the same sequences would hold had no block been handed
        back (every group's table as long as the context)."""
        return {
            "kv_bytes_resident": sum(
                (g.allocator.total_blocks - g.allocator.free_blocks)
                * g.allocator.bytes_per_block for g in self.groups),
            "kv_bytes_unreleased": sum(
                len(seq.kv_blocks) for seq in self._seqs.values())
            * sum(g.allocator.bytes_per_block for g in self.groups)}

    # -- reservation ledger (docs/SERVING.md "Admission and preemption") ----
    def _unfilled(self, uid: int, total: int) -> int:
        seq = self._seqs.get(uid)
        have = len(seq.kv_blocks) if seq is not None else 0
        return max(0, total - have)

    def reserved_unfilled(self) -> int:
        """Blocks the reserved sequences are still entitled to allocate —
        the ledger's claim against ``available_blocks``. Recomputed per
        read: the ledger only ever holds admitted + parked sequences
        (bounded by the ragged seat count, dozens), so the walk is noise
        next to the forward each scheduler step runs."""
        return sum(self._unfilled(uid, total)
                   for uid, total in self._reserved.items())

    def freeable_blocks_of(self, uid: int) -> int:
        """Blocks that would actually return to ``available_blocks`` if
        this sequence were flushed right now: private blocks (the
        sequence holds the only reference) plus cache-indexed blocks
        whose only OTHER reference is the cache's own (they become
        evictable). Prefix blocks other live sequences still share free
        NOTHING on flush — preemption victim selection must not count
        them, or a victim gets spilled for headroom that never
        materializes."""
        seq = self._seqs.get(uid)
        if seq is None:
            return 0
        n = 0
        for b in seq.kv_blocks:
            if b < 0:           # handed back behind the window already
                continue
            rc = self.allocator.ref_count(b)
            if rc == 1 or (rc == 2 and b in self._block_hash):
                n += 1
        return n

    def reservation_headroom(self) -> int:
        """``available_blocks`` minus the outstanding reservation claims:
        what a NEW reservation (or a preempted sequence's resume) can
        take without endangering an admitted sequence's future
        allocations. Negative only after a ``force_reserve``
        over-commitment (KV handoff imports) — the scheduler's
        preemption path restores it."""
        return self.available_blocks - self.reserved_unfilled()

    def try_reserve(self, uid: int, total_blocks: int) -> bool:
        """Reserve a sequence's total projected block need (prompt +
        generation budget, blocks it already holds — prefix-cache hits
        included — credited). False = shortfall: the caller defers the
        sequence instead of part-prefilling it into a wedge."""
        prior = self._reserved.pop(uid, None)
        need = self._unfilled(uid, int(total_blocks))
        if need > self.reservation_headroom():
            if prior is not None:
                self._reserved[uid] = prior
            return False
        self._reserved[uid] = int(total_blocks)
        return True

    def force_reserve(self, uid: int, total_blocks: int) -> None:
        """Record a reservation unconditionally — the KV-handoff import
        path, whose blocks are already resident when the ledger first
        hears of the sequence. May push headroom negative; the
        scheduler's preemption pass repairs that."""
        self._reserved[uid] = int(total_blocks)

    def release_reservation(self, uid: int) -> None:
        self._reserved.pop(uid, None)

    def reserved_total_blocks(self) -> int:
        """Sum of the reserved sequences' total projected needs — the
        resident half of the oversubscription-cap accounting."""
        return sum(self._reserved.values())

    @property
    def reserved_sequences(self) -> int:
        return len(self._reserved)

    # -- preemption spill store (docs/SERVING.md "Admission and preemption")
    def preempt_stash(self, uid: int, payload: Dict[str, object]) -> None:
        """Park an exported sequence's KV (``export_sequence`` payload)
        for a later resume. Slab bytes go through the KV tier when one
        is configured — int8 slabs under kv_quant ride the 4x
        compression, host overflow demotes to disk, and a dropped or
        corrupt entry degrades the resume to a greedy re-prefill — else
        they stay in host RAM on this store."""
        meta = {k: payload[k] for k in ("seen_tokens", "block_size",
                                        "kv_quant", "n_blocks")}
        # representation dtype axis (int8/fp8_e4m3) — absent only in
        # pre-dtype payloads, which were int8 by construction
        meta["kv_quant_dtype"] = payload.get("kv_quant_dtype", "int8")
        if "state" in payload:
            meta["state"] = payload["state"]
        if self._tier is not None:
            # not a prefix-cache spill: keep the per-block tier counters
            # honest (sequences_preempted counts these instead)
            self._tier.put(("__preempt__", uid), payload["slabs"],
                           _count_spill=False)
            meta["in_tier"] = True
        else:
            meta["slabs"] = payload["slabs"]
        self._preempt_store[uid] = meta

    def preempt_restore_payload(self, uid: int) -> Optional[Dict[str, object]]:
        """Take a parked sequence's export payload back (one-shot).
        ``None`` = nothing parked, or the tier dropped/corrupted the
        entry — the caller re-prefills (byte-lossless under greedy)."""
        meta = self._preempt_store.pop(uid, None)
        if meta is None:
            return None
        meta = dict(meta)
        if meta.pop("in_tier", False):
            slabs = (self._tier.get(("__preempt__", uid))
                     if self._tier is not None else None)
            if slabs is None:
                return None
            meta["slabs"] = slabs
        return meta

    def preempt_discard(self, uid: int) -> None:
        """Drop a parked payload (cancel/deadline/shutdown of a
        preempted sequence)."""
        meta = self._preempt_store.pop(uid, None)
        if meta is not None and meta.get("in_tier") and self._tier is not None:
            self._tier.discard(("__preempt__", uid))

    @property
    def preempted_parked(self) -> int:
        return len(self._preempt_store)

    # -- prefix cache --------------------------------------------------------
    @property
    def evictable_blocks(self) -> int:
        """Cached blocks whose only reference is the cache's own.
        Maintained incrementally (share on match / release on flush /
        eviction are the only transitions) — the admission path reads
        this once per candidate per step."""
        if not self.prefix_cache_enabled:
            return 0
        return self._evictable

    @property
    def available_blocks(self) -> int:
        """Blocks an allocate can obtain: free + evictable (admission
        control must count reclaimable cache residency, or a warm cache
        would wedge the scheduler on KVCacheLimitExceeded forever)."""
        return self.allocator.free_blocks + self.evictable_blocks

    def occupancy(self) -> Dict[str, int]:
        """One snapshot of KV-pool occupancy: the allocator's block/byte
        counts plus the prefix-cache view (evictable = reclaimable cached
        blocks, available = what an allocate can actually obtain). The
        serving layer publishes this as ``kv_blocks_in_use`` /
        ``kv_bytes_in_use`` gauges."""
        occ = self.allocator.occupancy()
        # blocks are the first group's (what ``num_blocks`` sized and
        # admission counts); bytes are every group's
        by_group = [dict(g.allocator.occupancy(), window=g.window,
                         layers=g.layers) for g in self.groups]
        for key in ("bytes_in_use", "bytes_total"):
            occ[key] = sum(g[key] for g in by_group)
        occ["evictable_blocks"] = self.evictable_blocks
        occ["available_blocks"] = occ["free_blocks"] + occ["evictable_blocks"]
        # per-tier residency (docs/SERVING.md "KV tiering"): zeros when
        # no tier is configured, so the serving gauges have one schema
        # either way
        tier = (self._tier.occupancy() if self._tier is not None
                else {"host_blocks": 0, "host_bytes": 0,
                      "disk_blocks": 0, "disk_bytes": 0})
        occ["kv_blocks_host_tier"] = tier["host_blocks"]
        occ["kv_bytes_host_tier"] = tier["host_bytes"]
        occ["kv_blocks_disk_tier"] = tier["disk_blocks"]
        occ["kv_bytes_disk_tier"] = tier["disk_bytes"]
        # a hybrid model's recurrent-state slots (zeros without one)
        occ["state_slots"] = self.state_slots
        occ["state_slots_used"] = self.state_slots - len(self._free_slots)
        occ["state_bytes"] = sum(int(leaf.nbytes)
                                 for leaf in self.state_cache.values())
        # the pools' bytes by leaf (k, v, a latent row, an index or a
        # compressed-key leaf beside them) and the state's
        occ["leaf_bytes"] = {name: int(leaf.nbytes) for name, leaf in
                             {**self.kv_cache, **self.state_cache}.items()}
        # by layer group (the keys above are the first group's): window,
        # layers, and the allocator's own snapshot
        occ["groups"] = by_group
        occ["blocks_released"] = self.blocks_released
        return occ

    def prefix_stats(self) -> Dict[str, int]:
        return dict(self._stats)

    def match_prefix(self, uid: int,
                     prompt_tokens: Sequence[int]) -> int:
        """Match a new sequence's prompt against the cache block-by-block.

        Shares every leading full block whose chain hash is indexed, seeds
        the sequence's ``seen_tokens`` at the matched length, and returns
        it. The walk is capped at ``len(prompt) - 1`` so at least one
        token is always left to prefill — the forward that produces the
        first logits. No-op (returns 0, creates nothing) when the cache is
        disabled or the sequence already has state.
        """
        if not self.prefix_cache_enabled:
            return 0
        if self.recurrent:      # enabled on a built engine: refuse here
            self.refuse_recurrent("the prefix cache (match_prefix)")
        if self.compressed:
            self.refuse_compressed("the prefix cache (match_prefix)")
        seq = self.get_or_create_sequence(uid)
        if seq.seen_tokens > 0 or seq.kv_blocks:
            return seq.seen_tokens
        self._stats["queries"] += 1
        limit = len(prompt_tokens) - 1
        matched: List[int] = []
        h = 0
        n = 0
        while n + self.block_size <= limit:
            key = (h, tuple(prompt_tokens[n:n + self.block_size]))
            b = self._index.get(key)
            if b is None and self._tier is not None:
                # tiered KV memory (docs/SERVING.md "KV tiering"): a
                # device miss may be a spilled run — chain keys are
                # computable from the prompt alone, so the whole
                # contiguous spilled run restores in ONE batched
                # scatter per pool tensor, then the walk re-reads the
                # index and continues as if it had hit
                if self._restore_chain(key, prompt_tokens, n, limit):
                    b = self._index.get(key)
            if b is None:
                self._stats["misses"] += 1
                break
            self._index.move_to_end(key)     # LRU touch
            if self.allocator.ref_count(b) == 1:
                self._evictable -= 1         # about to gain a sequence ref
            # share NOW (not batched at the end): a tier restore later in
            # this walk may trigger eviction, and an already-matched
            # block held only by the cache's ref would be reclaimable —
            # the sequence ref pins it for the rest of the walk
            self.allocator.share([b])
            matched.append(b)
            h = hash(key)
            n += self.block_size
            self._stats["hits"] += 1
        if matched:
            seq.kv_blocks.extend(matched)
            seq.seen_tokens = n
            seq.chain_hash = h
            seq.hashed_blocks = len(matched)
            self._stats["tokens_saved"] += n
        return n

    def record_tokens(self, seq: DSSequenceDescriptor,
                      tokens: Sequence[int], in_flight: int = 0) -> None:
        """Advance the sequence's hash chain with tokens just written to
        its KV blocks; each block that becomes full is registered in the
        index (prompt and generated tokens alike — a later request whose
        prompt extends this conversation reuses both). ``in_flight``: the
        tokens written behind these whose ids are not known yet (they
        follow in a call of their own)."""
        if not self.prefix_cache_enabled:
            return
        # chain-state consistency guard: hashing is only valid when the
        # chain covers the sequence from position 0 (a sequence that was
        # mid-flight when the cache got enabled would otherwise register
        # its content under wrong positions). An inconsistent sequence
        # skips without extending state, so it stays skipped.
        if (seq.hashed_blocks * self.block_size + len(seq.pending_tokens)
                != seq.seen_tokens - len(tokens) - in_flight):
            return
        seq.pending_tokens.extend(int(t) for t in tokens)
        while len(seq.pending_tokens) >= self.block_size:
            chunk = tuple(seq.pending_tokens[:self.block_size])
            del seq.pending_tokens[:self.block_size]
            key = (seq.chain_hash, chunk)
            seq.chain_hash = hash(key)
            block = seq.kv_blocks[seq.hashed_blocks]
            seq.hashed_blocks += 1
            if block >= 0:      # not handed back behind the window since
                self._register(key, block)

    def _register(self, key: tuple, block: int) -> None:
        if key in self._index or block in self._block_hash:
            return          # content already cached / block already indexed
        if (self.prefix_cache_max_blocks
                and len(self._index) >= self.prefix_cache_max_blocks
                and not self._evict(1)):
            return          # cache full of in-use blocks: skip registration
        self.allocator.share([block])        # the cache's own reference
        self._index[key] = block
        self._block_hash[block] = key
        # the registering sequence still holds its reference, so the block
        # enters the index referenced (not evictable) — it becomes
        # evictable in flush_sequence when the last sequence ref drops

    def _evict(self, n: int) -> int:
        """Drop up to ``n`` LRU unreferenced cached blocks; returns how
        many were evicted (their cache reference released → free list).

        With a KV tier configured (docs/SERVING.md "KV tiering") each
        evicted block's slab bytes spill to the host tier under its
        index key before the id returns to the free pool — safe even
        though release precedes the copy, because JAX arrays are
        immutable: the batched ``jnp.take`` below snapshots the pool
        content as of this call, and nothing rewrites the pool until a
        later forward. Only unreferenced full indexed blocks ever reach
        this path, so a referenced or partial block can never spill."""
        evicted = 0
        spill: List[tuple] = []         # (index key, block id)
        for key in list(self._index):
            if evicted >= n:
                break
            b = self._index[key]
            if self.allocator.ref_count(b) == 1:
                del self._index[key]
                del self._block_hash[b]
                if self._tier is not None:
                    spill.append((key, b))
                self.allocator.release([b])
                self._evictable -= 1
                self._stats["evictions"] += 1
                evicted += 1
        if spill:
            self._spill_blocks(spill)
        return evicted

    # -- tiered KV memory (docs/SERVING.md "KV tiering") ---------------------
    def configure_kv_tier(self, enabled: bool, host_bytes: int = 64 << 20,
                          disk_path: Optional[str] = None,
                          disk_bytes: int = 0) -> None:
        """Build (or tear down) the host-RAM/disk spill tier behind the
        prefix cache. Enabling requires the prefix cache — spill happens
        at cache eviction and restore at match, so a tier without the
        cache could never see a block. Disabling drops every spilled
        entry (and its disk files); re-enabling starts empty."""
        if self._tier is not None:
            self._tier.close()
            self._tier = None
        self._restore_times.clear()
        if not enabled:
            return
        if self.recurrent:
            self.refuse_recurrent("the KV tier")
        if self.headless:
            self.refuse_latent("the KV tier")
        if self.compressed:
            self.refuse_compressed("the KV tier")
        if not self.prefix_cache_enabled:
            raise ValueError(
                "kv_tier requires the prefix cache: spill/restore happen "
                "at prefix-cache eviction/match (enable prefix_cache "
                "first)")
        from ..kv_tier import TieredKVStore

        self._tier = TieredKVStore(host_bytes, disk_path=disk_path,
                                   disk_max_bytes=disk_bytes)

    @property
    def kv_tier_enabled(self) -> bool:
        return self._tier is not None

    def _spill_blocks(self, spill: List[tuple]) -> None:
        """Copy evicted blocks' slabs device→host into the tier. One
        batched gather per pool tensor with the host copies started
        async for all slabs before any is materialized (the
        export_sequence idiom), then one tier entry per block."""
        ids = jnp.asarray([b for _, b in spill], dtype=jnp.int32)
        arrs = {name: jnp.take(pool, ids, axis=1)
                for name, pool in self.kv_cache.items()}
        for a in arrs.values():
            try:
                a.copy_to_host_async()
            except Exception:       # backend without async host copy
                pass
        host = {name: np.asarray(a) for name, a in arrs.items()}
        for i, (key, _) in enumerate(spill):
            self._tier.put(key, {name: host[name][:, i] for name in host})

    def _restore_chain(self, first_key: tuple, prompt_tokens: Sequence[int],
                       n: int, limit: int) -> int:
        """Restore the contiguous spilled run starting at ``first_key``:
        look the chain ahead (key ``i+1`` is ``hash(key_i)`` + the next
        token block — computable from the prompt alone, no device data
        needed), pop every consecutive tier entry, and scatter them all
        back in ONE batched ``.at[:, ids].set`` per pool tensor — the
        per-block dispatch overhead is what would otherwise eat the
        saved prefill at small block sizes. The scatters are dispatched
        asynchronously (JAX async dispatch): the call returns with the
        copies in flight and the forward that later reads the pool
        orders itself after them, so other requests' work overlaps the
        restore. Each restored block re-registers under its original
        key; blocks the pool has no room for are readmitted to the tier
        (the match then degrades to a re-prefill from that point,
        exactly the tier-less behavior). Returns how many blocks were
        restored."""
        bs = self.block_size
        h, pos = first_key[0], n
        # cap the lookahead at what the pool could possibly hold BEFORE
        # popping anything: a chain longer than free+evictable would
        # otherwise pop (and disk-read, CRC-check, then readmit and
        # disk-REWRITE) a tail that can never fit — O(chain) disk churn
        # per repeat request in exactly the pool-smaller-than-working-set
        # regime the tier exists for
        budget = self.allocator.free_blocks + self.evictable_blocks
        if self.prefix_cache_max_blocks:
            budget = min(budget,
                         max(0, self.prefix_cache_max_blocks
                             - len(self._index)) + self.evictable_blocks)
        if budget <= 0:
            if first_key in self._tier:
                # the tier HAS the block but the pool can't take it:
                # that is a miss the serving path experienced, even
                # though nothing was popped
                self._tier.stats["misses"] += 1
            return 0
        keys: List[tuple] = []
        entries: List[Dict[str, np.ndarray]] = []
        while pos + bs <= limit and len(entries) < budget:
            key = (h, tuple(prompt_tokens[pos:pos + bs]))
            if key in self._index:
                break               # back in device: the walk takes over
            entry = self._tier.get(key)
            if entry is None:
                break
            keys.append(key)
            entries.append(entry)
            h = hash(key)
            pos += bs
        if not entries:
            return 0
        t0 = time.perf_counter()
        m = len(entries)
        short = m - self.allocator.free_blocks
        if short > 0:
            self._evict(short)      # colder residents spill to make room
        m = min(m, self.allocator.free_blocks)
        if self.prefix_cache_max_blocks:
            allowed = self.prefix_cache_max_blocks - len(self._index)
            if allowed < m:
                self._evict(m - allowed)
                allowed = self.prefix_cache_max_blocks - len(self._index)
            m = min(m, max(0, allowed), self.allocator.free_blocks)
        for key, entry in zip(keys[m:], entries[m:]):
            # no room: keep them for a calmer moment (readmit keeps the
            # tier's hit/miss/spill counters describing what happened)
            self._tier.readmit(key, entry)
        if m <= 0:
            return 0
        blocks = self.allocator.allocate(m)
        ids = jnp.asarray(blocks, dtype=jnp.int32)
        for name, pool in self.kv_cache.items():
            stacked = np.stack([entries[i][name] for i in range(m)], axis=1)
            self.kv_cache[name] = pool.at[:, ids].set(
                jnp.asarray(stacked, dtype=pool.dtype))
        for key, b in zip(keys[:m], blocks):
            self._index[key] = b
            self._block_hash[b] = key
            self._evictable += 1    # only the cache's ref so far; the
            #                         match hit path shares + decrements
        self._tier.stats["restored"] += m
        self._restore_times.append(time.perf_counter() - t0)
        if len(self._restore_times) > 4096:     # bounded when undrained
            del self._restore_times[:2048]
        return m

    def tier_stats(self) -> Dict[str, int]:
        """Monotonic spill/restore/drop counters plus current host/disk
        occupancy — all zeros (same shape) without a tier, so consumers
        (the replica's delta publish) need no feature check."""
        from ..kv_tier import empty_tier_stats

        if self._tier is None:
            return empty_tier_stats()
        out = dict(self._tier.stats)
        out.update(self._tier.occupancy())
        return out

    def drain_restore_times(self) -> List[float]:
        """Wall-clock restore-batch dispatch durations (one per
        contiguous restored run) since the last drain — the serving
        layer observes them into ``kv_tier_restore_s``."""
        out, self._restore_times = self._restore_times, []
        return out

    # -- fleet KV locality (docs/SERVING.md "Fleet KV locality") -------------
    def prefix_digest(self, max_entries: int = 512) -> List[int]:
        """A bounded digest of the cached prefix content this replica
        could serve without prefilling: the chain hashes of the device
        index (MRU first — the entries most likely to survive until the
        routed request arrives) plus the host/disk tier's keys (newest
        first). The digest is advisory routing input: truncation or a
        raced eviction only costs a router credit its match walk would
        have earned, never correctness. Empty when the cache is off.

        The list() snapshots below are single C-level calls, the same
        cross-thread tolerance the serving layer's ``tier_stats`` reads
        already rely on — the router tick reads this while the replica
        worker mutates the index."""
        if not self.prefix_cache_enabled or max_entries <= 0:
            return []
        out: List[int] = []
        for key in reversed(list(self._index)):
            if len(out) >= max_entries:
                return out
            out.append(hash(key))
        if self._tier is not None:
            host_keys, disk_keys = self._tier.lru_keys()
            for keys in (host_keys, disk_keys):
                for key in reversed(keys):
                    if len(out) >= max_entries:
                        return out
                    if key and key[0] == "__preempt__":
                        continue    # parked sequences aren't prefix content
                    out.append(hash(key))
        return out

    def export_prefix_blocks(self, max_blocks: int = 64) -> List[tuple]:
        """Device→host copies of the hottest cached prefix blocks, MRU
        first, as ``(index_key, {pool_name: per-block ndarray})`` pairs
        in tier-entry format — the donor side of replica warm-up. One
        batched ``jnp.take`` gather per pool tensor (the
        ``_spill_blocks`` idiom); the donor's own index is untouched.
        Empty when the cache is off or empty."""
        if not self.prefix_cache_enabled or max_blocks <= 0:
            return []
        pairs = [(key, b) for key, b
                 in reversed(list(self._index.items()))][:max_blocks]
        if not pairs:
            return []
        ids = jnp.asarray([b for _, b in pairs], dtype=jnp.int32)
        arrs = {name: jnp.take(pool, ids, axis=1)
                for name, pool in self.kv_cache.items()}
        for a in arrs.values():
            try:
                a.copy_to_host_async()
            except Exception:       # backend without async host copy
                pass
        host = {name: np.asarray(a) for name, a in arrs.items()}
        return [(key, {name: host[name][:, i] for name in host})
                for i, (key, _) in enumerate(pairs)]

    def import_prefix_blocks(self, entries: List[tuple]) -> int:
        """Seed the prefix cache with exported blocks (the grown-replica
        side of warm-up): allocate, scatter every slab back in ONE
        batched ``.at[:, ids].set`` per pool tensor (the
        ``_restore_chain`` idiom), and register each block under its
        original chain key as cache-referenced-only (evictable — warmed
        content yields to real traffic on pressure). Entries already
        indexed or beyond the free-block / ``prefix_cache_max_blocks``
        budget are skipped. Returns how many blocks landed."""
        if not self.prefix_cache_enabled or not entries:
            return 0
        budget = self.allocator.free_blocks
        if self.prefix_cache_max_blocks:
            budget = min(budget, max(0, self.prefix_cache_max_blocks
                                     - len(self._index)))
        take: List[tuple] = []
        for key, entry in entries:
            if len(take) >= budget:
                break
            if key in self._index:
                continue
            take.append((key, entry))
        if not take:
            return 0
        m = len(take)
        blocks = self.allocator.allocate(m)
        ids = jnp.asarray(blocks, dtype=jnp.int32)
        for name, pool in self.kv_cache.items():
            stacked = np.stack([take[i][1][name] for i in range(m)], axis=1)
            self.kv_cache[name] = pool.at[:, ids].set(
                jnp.asarray(stacked, dtype=pool.dtype))
        for (key, _), b in zip(take, blocks):
            self._index[key] = b
            self._block_hash[b] = key
            self._evictable += 1    # the allocate ref is the cache's ref,
            #                         exactly as in _restore_chain
        return m

    def clear_prefix_cache(self) -> None:
        """Drop every index entry, releasing the cache's references.
        Blocks still shared by live sequences stay allocated until those
        sequences flush; unreferenced ones return to the free list. A
        configured KV tier is emptied too (its entries are keyed by the
        chain hashes this wipe invalidates only in spirit — content keys
        stay valid — but a cleared cache should not keep shadow
        residency in host RAM)."""
        for key, b in list(self._index.items()):
            self.allocator.release([b])
        self._index.clear()
        self._block_hash.clear()
        self._evictable = 0
        if self._tier is not None:
            self._tier.clear()
