"""InferenceEngineV2 — FastGen-style ragged continuous-batching engine.

Counterpart of reference ``inference/v2/engine_v2.py:26``
(``InferenceEngineV2``: ``put`` :89 runs one forward over a ragged batch,
``query``/``can_schedule`` :161 for admission control, ``flush`` frees a
sequence's KV blocks). The serving loop on top (Dynamic SplitFuse) lives in
``scheduler.py`` — in the reference that loop is DeepSpeed-MII.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...models.mixers import KINDS, kinds_of
from ...models.mixers.base import keys_and_pairs as _keys_and_pairs
from ...models.mixers.base import narrow
from ...models.transformer import CausalLM
from ...ops import gated_delta
from ...ops import paged_attention as pa
from ...telemetry.tracer import NOOP_TRACER
from ...utils.logging import logger
from .paged_model import PagedCausalLM, fuse_qkv, split_qkv
from .ragged import BlockedAllocator, DSStateManager, RaggedBatchWrapper
from .scheduling_utils import SchedulingError, SchedulingResult


class RaggedInferenceEngineConfig:
    def __init__(self, max_ragged_batch_size: int = 768,
                 max_ragged_sequence_count: int = 32,
                 max_chunk_tokens: int = 256,
                 kv_blocks: int = 512, kv_block_size: int = 16,
                 max_tracked_sequences: int = 256,
                 enable_prefix_cache: bool = False,
                 prefix_cache_max_blocks: Optional[int] = None,
                 kv_quant_enabled: bool = False,
                 kv_quant_dtype: str = "int8",
                 kv_quant_scale_granularity: str = "block",
                 weight_quant_enabled: bool = False,
                 weight_quant_dtype: str = "int8",
                 weight_quant_block: int = 128,
                 weight_quant_skip: Optional[Sequence[str]] = None,
                 kv_tier_enabled: bool = False,
                 kv_tier_host_bytes: int = 64 * 1024 * 1024,
                 kv_tier_disk_path: Optional[str] = None,
                 kv_tier_disk_bytes: int = 0,
                 admission_reservation: bool = False,
                 admission_oversubscription_factor: float = 1.0,
                 admission_preemption_enabled: bool = False,
                 admission_victim_policy: str = "lowest_class",
                 admission_max_preemptions_per_seq: int = 2,
                 compile_ahead: int = 0):
        self.max_ragged_batch_size = max_ragged_batch_size
        self.max_ragged_sequence_count = max_ragged_sequence_count
        self.max_chunk_tokens = max_chunk_tokens
        self.kv_blocks = kv_blocks
        self.kv_block_size = kv_block_size
        self.max_tracked_sequences = max_tracked_sequences
        # prefix cache (docs/SERVING.md "Prefix caching"): share full KV
        # blocks between sequences with identical leading tokens
        self.enable_prefix_cache = enable_prefix_cache
        self.prefix_cache_max_blocks = prefix_cache_max_blocks
        # int8 KV-cache quantization (docs/SERVING.md "KV quantization"):
        # pools stored int8 with per-(layer, block, kv-head) scales —
        # a fixed HBM byte budget buys ~2x the blocks (kv_quant.py)
        self.kv_quant_enabled = kv_quant_enabled
        self.kv_quant_dtype = kv_quant_dtype
        self.kv_quant_scale_granularity = kv_quant_scale_granularity
        # int8/fp8 weight serving (docs/SERVING.md "Weight
        # quantization"): the CausalLM param tree is quantized ONCE at
        # engine build (inference/v2/weight_quant.py) and every matmul
        # runs from the quantized tree — ~3.9x fewer resident param
        # bytes vs fp32 and the per-step HBM weight stream cut with it
        self.weight_quant_enabled = weight_quant_enabled
        self.weight_quant_dtype = weight_quant_dtype
        self.weight_quant_block = weight_quant_block
        self.weight_quant_skip = (list(weight_quant_skip)
                                  if weight_quant_skip is not None else [])
        # tiered KV memory (docs/SERVING.md "KV tiering"): spill evicted
        # prefix-cache blocks to a bounded host-RAM tier (optionally
        # overflowing to disk) and restore them on a later prefix match
        # instead of re-prefilling — requires enable_prefix_cache
        self.kv_tier_enabled = kv_tier_enabled
        self.kv_tier_host_bytes = kv_tier_host_bytes
        self.kv_tier_disk_path = kv_tier_disk_path
        self.kv_tier_disk_bytes = kv_tier_disk_bytes
        # admission overhaul (docs/SERVING.md "Admission and
        # preemption"): total-block reservation admission in the
        # scheduler — a sequence's whole projected KV need is reserved
        # before its first prefill chunk, so N concurrent partial
        # prefills can never exhaust the pool with none able to finish
        # — plus preemption that spills a victim's KV to the tier and
        # resumes it later via import + submit_prefilled. Off (the
        # default) keeps the chunk-by-chunk admission byte for byte.
        self.admission_reservation = admission_reservation
        self.admission_oversubscription_factor = \
            admission_oversubscription_factor
        self.admission_preemption_enabled = admission_preemption_enabled
        self.admission_victim_policy = admission_victim_policy
        self.admission_max_preemptions_per_seq = \
            admission_max_preemptions_per_seq
        # compile every forward a put can ask for when the engine is
        # built, on this many threads at once, instead of one after the
        # other at each shape's first put (docs/SERVING.md "Compiling
        # ahead"). 0 (the default): compile at first use
        self.compile_ahead = int(compile_ahead)


#: Positions a dense forward computes for nothing. It streams every weight
#: once, whatever its shape, and multiplies each with every position: on a
#: v5e (197 TFLOP/s over 819 GB/s of bf16) the stream is the cost up to
#: about 240 positions -- one *weight pass*. Well inside that, padding is
#: free: a put whose padded ``[S, C]`` bucket holds no more positions than
#: this runs whole, whatever rows fill it, and the chunk part of a merged
#: forward is no narrower (every bucket is a program to compile, a merged
#: one with two traces of the paged kernel): ``_forward_groups``.
_FREE_POSITIONS = 128


#: keys of ``engine.last_put`` that ride on the scheduler's ``forward``
#: span only (by prefix): ``stage`` keeps the keys the benchmark's wrapper has
FORWARD_ONLY = ("kv_blocks_live", "kv_table_slots", "kv_blocks_released",
                "kv_bytes_", "kv_g", "attn_steps", "attn_turns")
#: ``ops.paged_attention.grid_steps``' four counts, as ``last_put`` names them
_ATTN_COUNTS = ("attn_steps", "attn_steps_primed", "attn_turns",
                "attn_turns_unmasked")


#: a one-token row's token when its sequence's next token is still on
#: the device: the forward takes it from the sequence's slot of
#: ``InferenceEngineV2.next_ids`` (``PagedCausalLM._forward``)
DEVICE_TOKEN = -1


class PutLogits:
    """What a put returns: its rows' logits, ``[len(uids), vocab]`` (or
    ``[len(uids), W, vocab]``), as a handle. The forwards' outputs stay on
    the device, padded rows and all, and nothing is copied until someone
    asks (``np.asarray``, an index, ``prefetch``); the rows are then cut
    and, where the put ran as several forwards, glued in the put's row
    order on the host -- on the device either would be one more small
    program for every row count a step can have.

    ``next_tokens()`` is the greedy draw over each row's last logits, read
    from the engine's next-token buffer as this put left it (one small
    copy, asked for when the put was dispatched)."""

    def __init__(self, parts, order, next_ids, slots, ran_dry=False):
        self.parts = parts              # [(device logits, real rows)]
        # whether the device had finished all it had been given when this
        # put's first forward was handed over (``put`` asks, not waiting)
        self.ran_dry = ran_dry
        # (a merged forward's rows are its wide row, then the others)
        self.order = np.argsort(order) \
            if list(order) != sorted(order) else None
        self.shape = (len(order),) + tuple(parts[0][0].shape[1:])
        self.dtype = parts[0][0].dtype
        self._ids, self._slots = next_ids, slots
        self._whole = None

    def prefetch(self) -> None:
        """Start the logits' copy to the host behind the forward, for a
        caller that will read them: the read then waits once, for the
        bytes, and not first for the program and then for the copy."""
        for part, _ in self.parts or ():
            part.copy_to_host_async()

    def next_tokens(self) -> np.ndarray:
        return np.asarray(self._ids)[self._slots]

    def __array__(self, dtype=None, copy=None):
        if self._whole is None:
            rows = [np.asarray(part)[:n] for part, n in self.parts]
            whole = rows[0] if len(rows) == 1 else np.concatenate(rows)
            self._whole = whole if self.order is None else whole[self.order]
            self.parts = None
        whole = self._whole
        return whole if dtype is None else whole.astype(dtype)

    def __len__(self):
        return self.shape[0]

    def __getitem__(self, index):
        return np.asarray(self)[index]


class InferenceEngineV2:
    def __init__(self, model: Optional[CausalLM] = None, params=None,
                 config: Optional[RaggedInferenceEngineConfig] = None,
                 checkpoint_path: Optional[str] = None, mesh=None):
        self.config = config or RaggedInferenceEngineConfig()
        if params is None and checkpoint_path is not None:
            # pretrained weights (reference engine_v2 builds its model from a
            # checkpoint via the layer-container DSL; here: models/convert.py)
            from ...models import convert

            model, params = convert.load_hf_checkpoint(checkpoint_path,
                                                       model=model)
        if model is None:
            raise ValueError("InferenceEngineV2 needs a model or checkpoint_path")
        self.model = model
        if params is None:
            params = model.init(jax.random.PRNGKey(0))

        # TP serving over a mesh with a tensor axis (reference
        # inference/v2/model_implementations/sharding/qkv.py:166): params
        # placed by the logical-axis TP rules, KV pool sharded over the
        # kv-head dim, attention shard_mapped inside PagedCausalLM.
        cache_sharding = None
        scale_sharding = None
        jmesh = None
        tp = 1
        if mesh is not None:
            from ...parallel import topology as topo_mod

            topo_obj = (mesh if isinstance(mesh, topo_mod.MeshTopology)
                        else topo_mod.MeshTopology(mesh))
            jmesh = topo_obj.mesh
            # raw meshes may lack a tensor axis entirely → unsharded serving
            tp = dict(jmesh.shape).get("tensor", 1)
            if tp <= 1:
                jmesh = None
                tp = 1
            elif model.cfg.is_latent:
                from ...models.hybrid import LatentKVUnsupported

                raise LatentKVUnsupported(
                    "TP serving splits the KV pool and the attention by "
                    "kv-head; a latent cache has no head to split (every "
                    "head reads the same row)")
            elif model.cfg.is_hybrid:
                from ...models.hybrid import RecurrentStateUnsupported

                raise RecurrentStateUnsupported(
                    "TP serving splits the KV pool and the attention by "
                    "head; a hybrid model's recurrent state and expert "
                    "share have no such split built yet")
        # int8/fp8 weight serving (docs/SERVING.md "Weight
        # quantization"): quantize the param tree ONCE, before TP
        # placement — so the scale planes are computed from the full
        # weights and then shard with their weight shards (the per-leaf
        # block divides the per-shard width; weight_quant.py).
        self._weight_quant_stats = None
        if self.config.weight_quant_enabled or jmesh is not None:
            # a tree another engine fused: neither path below reads it
            params = split_qkv(model.cfg, params)
        if self.config.weight_quant_enabled:
            from .weight_quant import quantize_weights

            params, self._weight_quant_stats = quantize_weights(
                model.cfg, params, dtype=self.config.weight_quant_dtype,
                block=self.config.weight_quant_block,
                skip=self.config.weight_quant_skip, tp=tp)
        if jmesh is None:
            # on one device q, k and v are one leaf and one matmul; a
            # quantized tree stays as it is, and under a tensor axis the
            # three shard by their own heads (``fuse_qkv``)
            params = fuse_qkv(params)
        else:
            from ...parallel.sharding import ZeroShardingPlan
            from .weight_quant import expand_spec_tree
            from jax.sharding import NamedSharding, PartitionSpec as P

            spec_tree = (model.param_specs()
                         if hasattr(model, "param_specs") else None)
            # quantized-weight nodes carry their spec onto both the
            # payload and the scale plane (the PR 6 KV scale-plane
            # treatment applied to weights)
            spec_tree = expand_spec_tree(spec_tree, params)
            plan = ZeroShardingPlan(topo_obj, 0, spec_tree)
            shardings = plan.params(jax.eval_shape(lambda: params))
            params = jax.tree.map(jax.device_put, params, shardings)
            cache_sharding = NamedSharding(
                jmesh, P(None, None, "tensor", None, None))
            # kv_quant scale planes [L, NB, KH] follow the pools'
            # kv-head split (paged_model extends the shard_map specs)
            scale_sharding = NamedSharding(jmesh, P(None, None, "tensor"))
        self.params = params

        cfg = model.cfg
        max_blocks_per_seq = -(-cfg.max_seq_len // self.config.kv_block_size)
        self._cache_sharding = cache_sharding
        self._scale_sharding = scale_sharding
        self.state_manager = self._build_state_manager()
        self.paged = PagedCausalLM(
            model, self.config.kv_block_size, max_blocks_per_seq, mesh=jmesh,
            max_batch_tokens=self.config.max_ragged_batch_size)
        # a hybrid model's chunks are padded to the delta rule's tile at
        # least: narrower ones would be programs of their own that do the
        # same tile's work
        self.batch = RaggedBatchWrapper(
            self.config.max_ragged_sequence_count,
            self.config.max_chunk_tokens, max_blocks_per_seq,
            min_chunk=gated_delta.TILE if cfg.is_hybrid else 1,
            groups=len(self.state_manager.groups))
        # the most bucket positions a put runs as one padded forward, and
        # past it the merged programs, tokens' shape -> rows
        # (``_forward_groups``); a hybrid model's chunk rows never share a
        # forward
        self._free_positions = 0 if cfg.is_hybrid else _FREE_POSITIONS
        self._merged_rows = self._merged_programs()
        # what the last put staged, counted where the work happens (plain
        # ints; the scheduler copies them into its span attrs when traced):
        # the bucket [S, C] the forward ran at, its real rows and valid
        # tokens, the keys those rows' queries may see and the query-key
        # pairs (the paged kernel's bytes and FLOPs follow from them), the
        # table blocks those keys fill beside the slots of the bucket's
        # tables (what the kernel's walk covers, of what a walk of the
        # whole table would), and the pool's available blocks after
        # allocation
        self.last_put: Dict[str, int] = {}
        # the same, cumulative since the engine was built (pad ratio over
        # any interval = delta positions_computed / delta tokens_valid)
        self.put_totals: Dict[str, int] = {
            "forwards": 0, "positions_computed": 0, "tokens_valid": 0,
            "puts_split": 0}        # puts that ran as several forwards
        # a hybrid model's kinds (none: a uniform model), and what each
        # counts of a forward (a family's kinds share one count)
        mixers = [KINDS[kind] for kind in kinds_of(cfg)]
        self._counts = tuple(dict.fromkeys(
            mixer.count for mixer in mixers if mixer.count is not None))
        # ... and the names and prefixes of those counts in ``last_put``
        # that a put of several forwards sums
        self._record = tuple(name for mixer in mixers
                             for name in mixer.record)
        # the kinds of layer that attend through the paged kernel, as the
        # shapes its grid follows from beside a forward's rows
        self._walks = self._paged_walks()
        self._walks_behind = self._walks_behind_exit()
        self._groups_behind = self._groups_behind_exit()
        # ... the whole-context group among them: ``qk_pairs`` is its count
        self._full_behind = any(
            b and not group.window for b, group in zip(
                self._groups_behind, self.state_manager.groups))
        if cfg.is_hybrid:
            if cfg.moe_num_experts:     # its sparse FFNs' rows
                self.put_totals.update(moe_rows_routed=0, moe_rows_held=0)
            for mixer in mixers:
                self.put_totals.update(dict.fromkeys(mixer.totals, 0))
            if any(mixer.holds for mixer in mixers):
                # forwards whose bucket was narrow enough for its kinds to
                # hold their projections to rows (``mixers.base.held``)
                self.put_totals["forwards_held"] = 0
            if cfg.layer_runs is not None:
                # the positions that ran the layers behind the model's
                # last layer that writes a cache: a row's last alone where
                # the forward has an exit (``PagedCausalLM._forward_runs``)
                self.put_totals["xdec_rows"] = 0
                self._record += ("xdec_rows",)
                self._exits = cfg.exit_at() is not None
        else:
            # forwards whose q, k and v came out of one stacked weight
            # (``fuse_qkv``): all of an engine's, or none
            self.put_totals["forwards_qkv_fused"] = 0
            # forwards that held a chunk row and one-token rows, merged
            self.put_totals["forwards_merged"] = 0
            # (at debug: the logger writes to stdout, where the serving
            # scripts' callers read ``*_LISTENING`` as the first line)
            logger.debug(
                "InferenceEngineV2: qkv is %s", "one matmul on wqkv"
                if self.qkv_fused else "three matmuls (quantized weights "
                "or a tensor axis: wq / wk / wv stay apart)")
        if any(g.window for g in self.state_manager.groups):
            # blocks handed back behind a window while their sequence lived
            self.put_totals["kv_blocks_released"] = 0
        # the next token of every tracked sequence, drawn by the forward
        # that computed its last logits and kept on the device: one slot a
        # sequence (``DSSequenceDescriptor.id_slot``) and a scratch one
        # for padded rows, carried from forward to forward like the pool
        # (but not donated: the buffer a put returned stays readable while
        # the next put runs). A row of ``[DEVICE_TOKEN]`` reads its slot.
        self.next_ids = jnp.zeros((self.state_manager.id_slots + 1,),
                                  jnp.int32)
        if jmesh is not None:
            self.next_ids = jax.device_put(
                self.next_ids, NamedSharding(jmesh, P()))
        self._forward_jit = self.paged.forward
        # the scheduler's tracer, handed over when one is built on this
        # engine: every forward is a ``dispatch`` span (``_forward_rows``)
        self.tracer = NOOP_TRACER
        self._compile_ahead()

    @property
    def qkv_fused(self) -> bool:
        """Whether the parameter tree is in the serving layout
        (``fuse_qkv``): read from the tree, which is what the forward's
        trace reads."""
        return "wqkv" in self.params["layers"]

    def forward_shapes(self) -> List[Tuple[int, int]]:
        """The shape of ``tokens`` in every forward a default put can ask
        for: ``[1, C]``, ``[S, 1]``, and of a dense model the padded
        ``[S, C]`` of at most ``_FREE_POSITIONS`` and past them the merged
        ``[1, C + S]`` (``_forward_groups``)."""
        seqs, chunks = self.batch.buckets()
        return [(s, c) for s in seqs for c in chunks
                if s == 1 or c == 1 or s * c <= self._free_positions] \
            + list(self._merged_rows)

    def _merged_programs(self) -> Dict[Tuple[int, int], int]:
        """``{(1, C + S): S}`` over the ``[S, C]`` buckets past
        ``_FREE_POSITIONS``, ``C`` no narrower than that. The tokens' shape
        names a program (``_compile_ahead``, and whoever reads a trace by
        bucket), so a ``C + S`` that is a chunk bucket too, or another
        pair's sum, is left out and its puts run apart; with no more rows
        a batch than ``_FREE_POSITIONS`` there is none."""
        seqs, chunks = self.batch.buckets()
        taken = {(1, c) for c in chunks}
        rows = {}
        for s in seqs[1:] if self._free_positions else ():
            for c in chunks[1:]:
                shape = (1, self._merged_chunk(c) + s)
                if s * c > self._free_positions and rows.get(shape) != s \
                        and shape not in taken:
                    taken.add(shape)
                    rows[shape] = s
        return rows

    def _merged_chunk(self, chunk: int) -> int:
        """The width of a merged forward's chunk part, for a chunk of the
        bucket ``chunk``."""
        return max(chunk, min(self._free_positions, self.batch.max_chunk))

    def _merged_shape(self, rows: int,
                      width: int) -> Optional[Tuple[int, int]]:
        """The tokens' shape of the merged forward over ``rows`` rows, one
        of them ``width`` tokens wide and the others one; None where they
        run padded or apart."""
        seqs, chunk = self.batch.bucket(rows, width)
        shape = (1, self._merged_chunk(chunk) + seqs)
        if seqs * chunk > self._free_positions \
                and self._merged_rows.get(shape) == seqs:
            return shape
        return None

    def _compile_ahead(self) -> None:
        """Lower the forward at every shape of ``forward_shapes``, for the
        parameters and the cache as they are now, and compile what is
        lowered on ``config.compile_ahead`` threads meanwhile;
        ``paged.forward`` then runs the executable of the batch's shape.
        The lowering stays on this thread, one shape after the other:
        it is Python and would take turns anyway, and which program
        traces a shared inner function first is written into every
        program's source locations — lowered on threads, the persistent
        cache's keys differ from run to run (measured: 4 of 11 missed)."""
        self.paged.forward = jitted = self._forward_jit
        if not self.config.compile_ahead:
            return
        sm = self.state_manager
        width = self.batch.max_blocks_per_seq

        groups = len(sm.groups)

        def lowered(shape):
            s = self._merged_rows.get(shape, shape[0])
            ints = [shape, (s,), (s,),
                    (s, width) if groups == 1 else (groups, s, width),
                    (s,) if sm.recurrent else None,
                    self.next_ids.shape, (s,)]
            return jitted.lower(self.params, sm.forward_cache, *(
                i and jax.ShapeDtypeStruct(i, jnp.int32) for i in ints))

        with ThreadPoolExecutor(self.config.compile_ahead) as pool:
            compiling = {shape: pool.submit(lowered(shape).compile)
                         for shape in self.forward_shapes()}
        programs = {shape: c.result() for shape, c in compiling.items()}

        def forward(params, cache, tokens, *rest):
            return programs[tokens.shape](params, cache, tokens, *rest)

        self.paged.forward = forward

    def _build_state_manager(self) -> DSStateManager:
        """Fresh sequence registry + KV pools from the current config —
        the constructor path and ``configure_kv_quant``'s rebuild."""
        from .kv_quant import validate_kv_quant

        if self.config.kv_quant_enabled:
            validate_kv_quant(self.config.kv_quant_dtype,
                              self.config.kv_quant_scale_granularity)
        return DSStateManager(
            self.model.cfg, self.config.max_tracked_sequences,
            self.config.kv_blocks, self.config.kv_block_size,
            sharding=self._cache_sharding,
            enable_prefix_cache=self.config.enable_prefix_cache,
            prefix_cache_max_blocks=self.config.prefix_cache_max_blocks,
            kv_quant=self.config.kv_quant_enabled,
            kv_quant_dtype=self.config.kv_quant_dtype,
            scale_sharding=self._scale_sharding,
            kv_tier_enabled=self.config.kv_tier_enabled,
            kv_tier_host_bytes=self.config.kv_tier_host_bytes,
            kv_tier_disk_path=self.config.kv_tier_disk_path,
            kv_tier_disk_bytes=self.config.kv_tier_disk_bytes,
            # a hybrid model: one recurrent-state slot a sequence the
            # scheduler can have running
            state_slots=self.config.max_ragged_sequence_count,
            group_blocks=[self.window_pool_blocks(window) for window, _
                          in self.model.cfg.kv_groups()[1:]])

    def window_pool_blocks(self, window: int) -> int:
        """The pool of a further layer group (``kv_blocks`` sizes the
        first, whose K/V lives longest): the most blocks the group's
        sequences can hold at once — between puts a sequence keeps the
        blocks of its last ``window - 1`` positions (``window / bs + 1``
        at most), a put adds a block for every ``bs`` tokens of its
        budget and one a row, and ``max_ragged_sequence_count`` sequences
        run — so that the group is never the one that runs out. Capped
        at ``kv_blocks``: no group outgrows the first."""
        c = self.config
        if not window:
            return c.kv_blocks
        bs, seqs = c.kv_block_size, c.max_ragged_sequence_count
        return min(c.kv_blocks, seqs * (-(-window // bs) + 2)
                   + -(-c.max_ragged_batch_size // bs))

    # ----------------------------------------------------------- admission
    def can_schedule(self, uids: Sequence[int],
                     lengths: Sequence[int]) -> SchedulingResult:
        """Reference engine_v2.py:161: can this (uids, lengths) batch run?"""
        if len(uids) > self.config.max_ragged_sequence_count:
            return SchedulingResult.BatchSequenceLimitExceeded
        if sum(lengths) > self.config.max_ragged_batch_size:
            return SchedulingResult.BatchTokenLimitExceeded
        blocks_needed = slots_needed = 0
        for uid, n in zip(uids, lengths):
            if n > self.config.max_chunk_tokens:
                return SchedulingResult.SequenceTokenLimitExceeded
            seq = self.state_manager.get_sequence(uid)
            slots_needed += seq is None
            total = (seq.seen_tokens if seq else 0) + n
            if total > self.model.cfg.max_seq_len:
                return SchedulingResult.SequenceTokenLimitExceeded
            have = seq.cur_allocated_blocks if seq else 0
            need = -(-total // self.config.kv_block_size)
            blocks_needed += max(0, need - have)
        # available = free + LRU-evictable cached blocks (identical to the
        # free count when the prefix cache is disabled)
        if blocks_needed > self.state_manager.available_blocks \
                or self.state_manager.groups_short(blocks_needed):
            return SchedulingResult.KVCacheLimitExceeded
        if self.state_manager.recurrent and \
                slots_needed > self.state_manager.free_state_slots:
            return SchedulingResult.KVCacheLimitExceeded
        return SchedulingResult.Success

    def query(self, uid: int) -> Tuple[int, int]:
        """(seen_tokens, allocated_blocks) for a sequence (reference query)."""
        seq = self.state_manager.get_sequence(uid)
        if seq is None:
            return (0, 0)
        return (seq.seen_tokens, seq.cur_allocated_blocks)

    # -------------------------------------------------------------- serving
    def put(self, uids: Sequence[int],
            tokens_list: Sequence[Sequence[int]], *,
            verify_width: int = 0,
            defer_commit: bool = False) -> PutLogits:
        """Run one forward over the ragged batch; returns next-token logits
        [len(uids), vocab] (reference engine_v2.py:89) as a handle that
        copies nothing from the device until it is read (``PutLogits``:
        ``np.asarray``, an index; ``next_tokens()`` for the greedy draws
        alone). A one-token row may be ``[DEVICE_TOKEN]``: its token is
        then the one the sequence's last forward drew, which is still on
        the device (``next_ids``) — the same program either way.

        Speculative verification (spec/, docs/SERVING.md "Speculative
        decoding") uses two keyword extensions; the default call is
        byte-for-byte the historical path:

        - ``verify_width`` W > 0: return logits for each row's last W
          valid positions, right-aligned — [len(uids), W, vocab] with
          row i's last valid token at position W-1 — so the caller can
          read the target's greedy argmax at every draft offset without
          the engine materializing logits for the whole padded chunk. W
          is static per compiled program; callers should bucket it.
        - ``defer_commit``: advance ``seen_tokens`` (the KV was written)
          but do NOT advance the prefix-cache hash chain — the fed tokens
          may contain unverified drafts, and the index must never refer to
          content that a later ``trim_sequence`` rolls back. The caller
          commits the accepted prefix afterwards via :meth:`commit_tokens`.
        """
        widths = [len(t) for t in tokens_list]
        status = self.can_schedule(uids, widths)
        if status != SchedulingResult.Success:
            raise SchedulingError(status)
        groups = self._forward_groups(widths, verify_width)
        sm = self.state_manager
        outs, records = [], []
        # everything handed over before this put ends in this buffer
        ahead, ran_dry = self.next_ids, False
        for rows in groups:
            outs.append((self._forward_rows(
                [uids[i] for i in rows], [tokens_list[i] for i in rows],
                verify_width, defer_commit), len(rows)))
            if not records:
                # asked without waiting, the put's first forward just
                # handed over: had the device run dry before it got there?
                ran_dry = ahead.is_ready()
            records.append(self.last_put)
        # the draws' copy back is asked for now and follows the put's last
        # forward on the device's own queue
        self.next_ids.copy_to_host_async()
        result = PutLogits(
            outs, [i for rows in groups for i in rows], self.next_ids,
            [sm.get_sequence(uid).id_slot for uid in uids], ran_dry)
        if len(groups) == 1:
            return result
        # the put's record: its last (widest) forward's bucket, the sums
        # of what its forwards counted, and how many they were
        summed = ("rows", "valid_tokens", "kv_read_tokens", "qk_pairs",
                  "kv_blocks_live", "kv_table_slots",
                  *_ATTN_COUNTS,
                  "moe_rows_routed", "moe_rows_held", "kv_blocks_released") \
            + tuple(k for k in records[-1] if k.endswith(("_read_tokens",
                                                          "_qk_pairs"))
                    or k.startswith(self._record))
        self.last_put = dict(records[-1], forwards=len(records), **{
            k: sum(r.get(k, 0) for r in records) for k in summed
            if k in records[-1]})
        self.put_totals["puts_split"] += 1
        return result

    def _forward_groups(self, widths: Sequence[int],
                        verify_width: int = 0) -> List[List[int]]:
        """Which rows of a put run together in one forward, from their
        token counts. The forward pads its batch to an ``[S, C]`` bucket,
        so a chunk row beside S - 1 one-token rows costs S times its own
        work in every mixer and every matmul; as forwards of their own
        (``[S, 1]`` and ``[1, C]``), every weight is streamed twice. So:

        - a bucket of at most ``_FREE_POSITIONS`` runs whole, padded,
          whatever rows fill it: it costs the weight stream either way;
        - past it, a dense put's one-token rows and its **first** wide row
          run as one *merged* forward, first: the ``C + S`` positions laid
          end to end through everything that works position by position,
          the K/V write and the attention once a part
          (``PagedCausalLM._forward``). It is keyed as the padded one
          would be, by the bucket of the group's row count and of the
          wide row's width;
        - every further wide row runs as a ``[1, C]`` forward of its own.

        The parts' logits meet at the scheduler's one fetch
        (``PutLogits``). The rule reads buckets alone: whoever has put
        every ``[S, C]`` once with one wide row has run every program a
        later put can reach (``forward_shapes``).

        A hybrid model's rows never share a forward (measured on the chip
        at Qwen3-Next's widths, 8k of context: ``[8, 1024]`` 116 ms
        against ``[1, 1024]`` 45 ms + ``[8, 1]`` 4 ms; its one-token
        forward is 1.25-4 ms beside chunk forwards of 22-137 ms, and a
        shared pass would have to carry recurrent state and layer
        groups). A put that verifies drafts stays whole in its padded
        ``[S, W]`` bucket (a one-token part has no ``verify_width``
        positions), and so does one wide row alone. What the chip read,
        merged against apart: docs/SERVING.md "A put may be several
        forwards"."""
        everyone = [list(range(len(widths)))]
        wide = [i for i, n in enumerate(widths) if n > 1]
        ones = [i for i, n in enumerate(widths) if n == 1]
        seqs, chunk = self.batch.bucket(len(widths), max(widths))
        if not wide or (len(wide) == 1 and not ones) or verify_width \
                or seqs * chunk <= self._free_positions:
            return everyone
        if ones and self._merged_shape(1 + len(ones), widths[wide[0]]):
            return [[wide[0]] + ones] + [[i] for i in wide[1:]]
        return ([ones] if ones else []) + [[i] for i in wide]

    def _forward_rows(self, uids, tokens_list, verify_width: int,
                      defer_commit: bool) -> jnp.ndarray:
        """One forward over ``uids``' rows (``put``'s body)."""
        self.batch.clear()
        staged = []
        sm = self.state_manager
        groups = sm.groups
        valid = kv_read = qk_pairs = blocks_live = 0
        # by layer group, under its window: the keys the rows' queries may
        # see and the query-key pairs (what its layers' kernel calls read
        # and multiply), counted where a window can bound them
        group_read, group_pairs = [0] * len(groups), [0] * len(groups)
        block_size = self.config.kv_block_size
        for uid, toks in zip(uids, tokens_list):
            seq = sm.get_or_create_sequence(uid)
            sm.maybe_allocate_kv(seq, len(toks))
            rows = sm.table_rows(seq)
            self.batch.insert_sequence(
                uid, toks, seq.seen_tokens,
                rows[0] if len(groups) == 1 else rows)
            staged.append((seq, toks))
            n, seen = len(toks), seq.seen_tokens
            valid += n
            # behind the forward's exit a row's last position attends
            # alone: that one query's keys and pairs, whatever the width
            queries = ((seen, n), (seen + n - 1, min(n, 1)))
            read, pairs = _keys_and_pairs(0, *queries[self._full_behind])
            kv_read += read
            qk_pairs += pairs
            last = -(-(seen + n) // block_size)
            for g, group in enumerate(groups):
                # the blocks the kernel's walk covers: from the window's
                # first live block to the context's last
                blocks_live += last - sm.first_live_block(group.window, seen)
                read, pairs = _keys_and_pairs(
                    group.window, *queries[self._groups_behind[g]])
                group_read[g] += read
                group_pairs[g] += pairs

        # a wide row, first, and one-token rows past ``_FREE_POSITIONS``
        # are a merged forward (``_forward_groups``): the bucket it states
        # is its tokens' shape, [1, C + S]
        merged, width = None, len(tokens_list[0])
        if not verify_width and 1 < width == valid - len(staged) + 1:
            merged = self._merged_shape(len(staged), width)
        arrays = self.batch.finalize_merged(merged[1]) if merged \
            else self.batch.finalize()
        bucket_seqs, bucket_chunk = arrays["tokens"].shape
        table_rows = len(arrays["start_pos"])
        self.last_put = {
            "bucket_seqs": bucket_seqs, "bucket_chunk": bucket_chunk,
            "rows": len(staged), "valid_tokens": valid,
            "kv_read_tokens": kv_read, "qk_pairs": qk_pairs,
            "kv_blocks_live": blocks_live,
            "kv_table_slots": table_rows * len(groups)
            * arrays["block_tables"].shape[-1],
            "free_blocks": sm.available_blocks}
        self._count_attn_steps(arrays, bool(merged))
        totals = self.put_totals
        totals["forwards"] += 1
        if self.qkv_fused:
            totals["forwards_qkv_fused"] += 1
        if merged:
            totals["forwards_merged"] += 1
        if "forwards_held" in totals and narrow(
                self.model.cfg, bucket_seqs * bucket_chunk):
            totals["forwards_held"] += 1
        totals["positions_computed"] += bucket_seqs * bucket_chunk
        totals["tokens_valid"] += valid
        kv_cache = sm.forward_cache
        # the host's arrays go to the jitted call as they are: its own
        # argument path moves them in a fifth of the time four
        # ``jnp.asarray`` calls take (the wrapper makes new ones a forward,
        # so none is written again behind the transfer)
        slots = None
        if sm.recurrent:
            # each row's slot in the state tree; a padded row's is the
            # scratch slot behind the last
            slots = np.full((bucket_seqs,), sm.state_slots, np.int32)
            slots[:len(staged)] = [seq.state_slot for seq, _ in staged]
            # a hybrid model's put says its slots in use
            self.last_put["state_slots_used"] = \
                sm.state_slots - sm.free_state_slots
        # and its slot in the next-token buffer, likewise
        id_slots = np.full((table_rows,), sm.id_slots, np.int32)
        id_slots[:len(staged)] = [seq.id_slot for seq, _ in staged]
        args = (self.params, kv_cache, arrays["tokens"],
                arrays["start_pos"], arrays["n_tokens"],
                arrays["block_tables"], slots, self.next_ids, id_slots)
        if "moe_rows_routed" in self.put_totals:    # its sparse FFNs' rows
            self._count_routing(valid)
        if "xdec_rows" in totals:
            self.last_put["xdec_rows"] = len(staged) if self._exits \
                else valid
            totals["xdec_rows"] += self.last_put["xdec_rows"]
        for count in self._counts:      # and what its kinds count
            counts = count(self.model.cfg, staged, bucket_chunk, block_size)
            self.last_put.update(counts)
            for name in counts:
                if name in totals:
                    totals[name] += counts[name]
        # the unit of device work, named where it is handed over: one
        # ``dispatch`` span a forward, round the call alone, with that
        # forward's own counts (not the put's sums) and its place in the
        # engine's order of dispatch, which is the device's order of
        # execution (docs/OBSERVABILITY.md "XLA alignment"). One call site,
        # traced or not: a wrapper frame would change a Mosaic kernel's
        # compile-cache key
        tracer, attrs = self.tracer, None
        if tracer.enabled:
            attrs = {"ordinal": totals["forwards"],
                     "bucket_seqs": bucket_seqs, "bucket_chunk": bucket_chunk,
                     "rows": len(staged), "valid_tokens": valid,
                     # the one-token rows a merged forward carried
                     "merged_ones": len(staged) - 1 if merged else 0,
                     # one string: a tuple's commas would end the stat in
                     # the annotation's ``key=value,`` encoding
                     "uids": " ".join(str(u) for u in uids)}
            if verify_width:
                attrs["verify_width"] = int(verify_width)
            attrs.update(self._own_counts())    # this forward's, no sums
        # the forward consumes ``kv_cache`` (donated, written in place) and
        # hands the same memory back as ``new_cache``
        try:
            with tracer.span("dispatch", attrs=attrs):
                if verify_width:
                    logits, new_cache, next_ids = self.paged.forward_verify(
                        *args, verify_width=int(verify_width))
                else:
                    logits, new_cache, next_ids = self.paged.forward(*args)
        except Exception as e:
            if any(leaf.is_deleted() for leaf in kv_cache.values()):
                raise RuntimeError(
                    "the forward failed after it had consumed the donated "
                    "KV pool: every cached sequence is lost with it and "
                    "this engine cannot be retried — rebuild it "
                    "(serving marks the replica DEAD)") from e
            raise
        # commit sequence state only after the forward was dispatched: a
        # forward that fails before dispatch leaves the pool and
        # seen_tokens unchanged (the step can be retried) and — critically
        # — never registers blocks whose KV was never written in the
        # prefix-cache index, and hands no block back. Allocation above is
        # safe either way: the blocks belong to the sequence and return to
        # the pool at flush. (Assumes each uid appears at most once per
        # batch, which the scheduler guarantees.)
        sm.forward_cache = new_cache
        self.next_ids = next_ids
        released = 0
        for seq, toks in staged:
            seq.seen_tokens += len(toks)
            if not defer_commit:
                # a token that is still on the device is recorded when
                # its id has come back (``commit_tokens``)
                if toks[0] != DEVICE_TOKEN:
                    sm.record_tokens(seq, toks)
                # the blocks now wholly behind a window belong to no later
                # query: lengths say so, whatever the tokens are (a put
                # that verifies drafts may yet be trimmed: its release
                # waits for ``commit_tokens``)
                released += sm.release_behind(seq)
        self._record_groups(released, group_read, group_pairs)
        return logits

    def _paged_walks(self) -> List[Tuple[str, Dict[str, int]]]:
        """One entry a kind of layer whose attention is
        ``ops.paged_attention.paged_attention`` — a dense model's layers,
        a hybrid block's kinds that say so (``Mixer.paged_walk``) — with
        what that call's grid follows from beside its rows
        (``grid_steps``): its K pool's leaf (whose dtype the tiles
        follow), a TP shard's heads, the head size, the kind's window.
        No entry for a latent cache or a selection of blocks (other
        kernels), none at all where the call is the XLA gather (off the
        chip, or by the registry)."""
        cfg, tp = self.model.cfg, self.paged.tp
        heads, kv_heads, head_dim = cfg.paged_heads()
        shape = {"heads": heads // tp, "kv_heads": kv_heads // tp,
                 "head_dim": head_dim,
                 "block_size": self.config.kv_block_size}
        if self.paged._attn_raw is pa.paged_attention_xla \
                or not pa.pallas_supported(shape["heads"], shape["kv_heads"],
                                           head_dim):
            return []
        windows = [group.window for group in self.state_manager.groups]
        if cfg.layer_pattern is None:
            return [("k", dict(shape, window=windows[0]))]
        # a kind's layer group, as ``_forward_hybrid`` finds it
        walks = []
        for kind in kinds_of(cfg):
            if KINDS[kind].paged_walk:
                g = windows.index(int(cfg.sliding_window)
                                  if KINDS[kind].windowed else 0)
                walks.append((f"k{g or ''}", dict(shape, window=windows[g])))
        return walks

    def _own_counts(self) -> Dict[str, int]:
        """What a forward's ``dispatch`` span carries of ``last_put``
        beside the engine's own attrs: ``xdec_rows``, where the model
        counts it (the ``forward`` span holds a put's sums)."""
        return {"xdec_rows": self.last_put["xdec_rows"]} \
            if "xdec_rows" in self.last_put else {}

    def _walks_behind_exit(self) -> List[bool]:
        """For each of ``_paged_walks``' entries, whether the kind's
        calls come behind the forward's exit — every layer of the kind
        lies at ``cfg.exit_at()`` or behind it — where a chunk forward's
        call is one position a row (``PagedCausalLM._forward_runs``)."""
        cfg = self.model.cfg
        exit_at = cfg.exit_at() if cfg.is_hybrid else None
        if exit_at is None:
            return [False] * len(self._walks)
        return [exit_at <= min(
            (r, i) for r, (pattern, _) in enumerate(cfg.layer_runs)
            for i, k in enumerate(pattern) if k == kind)
            for kind in kinds_of(cfg) if KINDS[kind].paged_walk]

    def _groups_behind_exit(self) -> List[bool]:
        """For each layer group, whether every kind that walks its pool
        rows lies behind the forward's exit: a chunk row's keys and pairs
        in that group are then its last position's (``_forward_rows``)."""
        cfg = self.model.cfg
        windows = [group.window for group in self.state_manager.groups]
        exit_at = cfg.exit_at() if cfg.is_hybrid else None
        if exit_at is None:
            return [False] * len(windows)
        behind: Dict[int, bool] = {}
        for r, (pattern, _) in enumerate(cfg.layer_runs):
            for i, kind in enumerate(pattern):
                if KINDS[kind].paged_walk:
                    g = windows.index(int(cfg.sliding_window)
                                      if KINDS[kind].windowed else 0)
                    behind[g] = behind.get(g, True) and exit_at <= (r, i)
        return [behind.get(g, False) for g in range(len(windows))]

    def _count_attn_steps(self, arrays, merged: bool) -> None:
        """``attn_steps`` / ``attn_steps_primed`` / ``attn_turns`` /
        ``attn_turns_unmasked`` of ``last_put``: the live grid steps of
        this forward's paged-attention calls, one call a kind of layer
        (not a layer: the layers of a kind repeat it), those whose first
        turn the step before had fetched, the turns the steps fold and
        those of them folded without the mask — the kernel's rules on
        the rows the forward is handed
        (``ops.paged_attention.grid_steps``). A merged forward's calls
        are ``paged_model._parts``': row 0's chunk alone, then one
        position a row with row 0 a padded row. Two dozen array
        operations a call: a traced forward's alone, as its ``dispatch``
        attrs are."""
        if not (self._walks and self.tracer.enabled):
            return
        start, n_tokens = arrays["start_pos"], arrays["n_tokens"]
        width = arrays["tokens"].shape[1]
        calls = [(width, start, n_tokens)]
        if merged:
            dead = np.arange(len(start)) > 0
            calls = [(width - len(start), start[:1], n_tokens[:1]),
                     (1, start * dead, n_tokens * dead)]
        cache = self.state_manager.forward_cache
        counts = np.zeros(len(_ATTN_COUNTS), np.int64)
        # behind the exit a row's last valid position attends alone
        last = [(1, start + np.maximum(n_tokens - 1, 0),
                 np.minimum(n_tokens, 1))]
        for (leaf, shape), behind in zip(self._walks, self._walks_behind):
            for chunk, s, n in (last if behind and width > 1 else calls):
                counts += pa.grid_steps(
                    s, n, chunk=chunk, q_dtype=self.model.cfg.dtype,
                    pool_dtype=cache[leaf].dtype,
                    table_blocks=arrays["block_tables"].shape[-1], **shape)
        self.last_put.update(zip(_ATTN_COUNTS, map(int, counts)))

    def _record_groups(self, released: int, group_read, group_pairs) -> None:
        """The put's record by layer group, for a model that keeps more
        than one and for any model once a block has been handed back (a
        put inside its window keeps the record it had, key for key):
        blocks handed back by this put, each group's pool blocks in use
        of its total, the K/V bytes resident beside what the same
        sequences would hold unreleased, and each group's window-bounded
        keys and pairs."""
        sm = self.state_manager
        self._count_released(released)
        if len(sm.groups) == 1 and not sm.blocks_released:
            return
        record = {"kv_blocks_released": released, **sm.resident_bytes()}
        for g, group in enumerate(sm.groups):
            alloc = group.allocator
            record.update({f"kv_g{g}_window": group.window,
                           f"kv_g{g}_in_use": alloc.total_blocks
                           - alloc.free_blocks,
                           f"kv_g{g}_total": alloc.total_blocks,
                           f"kv_g{g}_read_tokens": group_read[g],
                           f"kv_g{g}_qk_pairs": group_pairs[g]})
        self.last_put.update(record)

    def _count_released(self, released: int) -> None:
        if "kv_blocks_released" in self.put_totals:
            self.put_totals["kv_blocks_released"] += released

    def _count_routing(self, valid_tokens: int) -> None:
        """``moe_rows_routed`` / ``moe_rows_held`` of a hybrid model's
        sparse FFNs: the (token, choice) pairs this forward routes — every
        valid token, top-k choices, each layer — and, of those, the pairs
        whose expert this model holds. The second is the *expectation*
        under even routing (routed x held / experts): the real count
        lives on the device and is not fetched."""
        cfg = self.model.cfg
        routed = valid_tokens * cfg.moe_top_k * cfg.num_sparse_layers
        held = cfg.moe_held_experts[1] if cfg.moe_held_experts \
            else cfg.moe_num_experts
        counts = {"moe_rows_routed": routed,
                  "moe_rows_held": routed * held // cfg.moe_num_experts}
        self.last_put.update(counts)
        for name, n in counts.items():
            self.put_totals[name] += n

    def flush(self, uid: int) -> None:
        self.state_manager.flush_sequence(uid)

    # ------------------------------------------------------- speculative
    def trim_sequence(self, uid: int, n_tokens: int) -> int:
        """Drop a sequence's trailing ``n_tokens`` from the KV cache —
        speculative-decoding rollback of rejected draft tokens. Returns
        the number of KV blocks released (see
        :meth:`DSStateManager.trim_sequence` for the prefix-cache
        interaction contract)."""
        return self.state_manager.trim_sequence(uid, n_tokens)

    def commit_tokens(self, uid: int, tokens: Sequence[int],
                      in_flight: int = 0) -> None:
        """Advance the prefix-cache hash chain with tokens a put left
        unrecorded: the second half of a ``put(defer_commit=True)`` step,
        called after rejected drafts were trimmed, and of a
        ``DEVICE_TOKEN`` row, called when the id has come back —
        ``in_flight``: the tokens of this sequence put since, which the
        chain does not hold yet either. No-op when the cache is
        disabled."""
        seq = self.state_manager.get_sequence(uid)
        if seq is not None:
            self.state_manager.record_tokens(seq, tokens, in_flight)
            # the release a put that deferred its commit left undone
            self._count_released(self.state_manager.release_behind(seq))

    # ----------------------------------------------------------- KV handoff
    def export_sequence(self, uid: int,
                        chunk_blocks: int = 0) -> Optional[Dict[str, object]]:
        """Host-RAM snapshot of a sequence's KV blocks (pool slabs +
        kv_quant scale planes + metadata) for disaggregated
        prefill→decode handoff — see
        :meth:`DSStateManager.export_sequence` (``chunk_blocks`` > 0 =
        the block-granularity streamed form). The sequence stays
        tracked; the caller :meth:`flush`\\ es once the payload is
        staged."""
        return self.state_manager.export_sequence(uid,
                                                  chunk_blocks=chunk_blocks)

    def import_sequence(self, uid: int, payload: Dict[str, object],
                        tokens: Sequence[int]) -> None:
        """Adopt an exported sequence's KV into this engine's pool and
        resume decoding from it byte-losslessly — see
        :meth:`DSStateManager.import_sequence`. Raises (leaving this
        engine untouched) on representation mismatch or KV pressure; the
        serving layer falls back to re-prefilling."""
        self.state_manager.import_sequence(uid, payload, tokens)

    # ---------------------------------------------- admission + preemption
    def configure_admission(self, reservation: bool,
                            oversubscription_factor: float = 1.0,
                            preemption_enabled: bool = False,
                            victim_policy: str = "lowest_class",
                            max_preemptions_per_seq: int = 2) -> None:
        """Stamp the admission-overhaul settings (docs/SERVING.md
        "Admission and preemption") onto a built engine — the serving
        layer's config-driven hook (``ServingConfig.admission``).
        Schedulers read these at construction, so call it before the
        replica (and its scheduler) is built — the ``ServingFrontend``
        replica-build path does."""
        if preemption_enabled and not reservation:
            raise ValueError(
                "admission preemption requires reservation admission "
                "(preemption is triggered by reservation shortfall)")
        self.config.admission_reservation = bool(reservation)
        self.config.admission_oversubscription_factor = \
            float(oversubscription_factor)
        self.config.admission_preemption_enabled = bool(preemption_enabled)
        self.config.admission_victim_policy = str(victim_policy)
        self.config.admission_max_preemptions_per_seq = \
            int(max_preemptions_per_seq)

    def try_reserve(self, uid: int, total_blocks: int) -> bool:
        """Reserve a sequence's total projected block need against the
        ledger — see :meth:`DSStateManager.try_reserve`."""
        return self.state_manager.try_reserve(uid, total_blocks)

    def force_reserve(self, uid: int, total_blocks: int) -> None:
        self.state_manager.force_reserve(uid, total_blocks)

    def release_reservation(self, uid: int) -> None:
        self.state_manager.release_reservation(uid)

    def reservation_headroom(self) -> int:
        """Blocks a new reservation can still claim — see
        :meth:`DSStateManager.reservation_headroom`."""
        return self.state_manager.reservation_headroom()

    def reserved_total_blocks(self) -> int:
        return self.state_manager.reserved_total_blocks()

    def freeable_blocks_of(self, uid: int) -> int:
        """Blocks a flush of this sequence would actually return to
        ``available_blocks`` — see
        :meth:`DSStateManager.freeable_blocks_of`."""
        return self.state_manager.freeable_blocks_of(uid)

    def preempt_stash(self, uid: int, payload: Dict[str, object]) -> None:
        """Park an exported sequence's KV for a later preemption resume
        — see :meth:`DSStateManager.preempt_stash`."""
        self.state_manager.preempt_stash(uid, payload)

    def preempt_restore_payload(self, uid: int) -> Optional[Dict[str, object]]:
        return self.state_manager.preempt_restore_payload(uid)

    def preempt_discard(self, uid: int) -> None:
        self.state_manager.preempt_discard(uid)

    def match_prefix(self, uid: int, prompt_tokens: Sequence[int]) -> int:
        """Prefix-cache lookup for a new sequence: share every cached
        leading full KV block of ``prompt_tokens`` and return the matched
        token count (the caller skips prefilling that many tokens).
        Returns 0 when the prefix cache is disabled — and, critically,
        creates no sequence state in that case."""
        return self.state_manager.match_prefix(uid, prompt_tokens)

    def prefix_stats(self) -> Dict[str, int]:
        """Monotonic prefix-cache counters: hits/misses (block lookups),
        evictions, tokens_saved, queries."""
        return self.state_manager.prefix_stats()

    def prefix_digest(self, max_entries: int = 512) -> List[int]:
        """Bounded chain-hash digest of the cached prefix content (device
        index + KV tier) — the fleet router's affinity input; see
        :meth:`DSStateManager.prefix_digest`."""
        return self.state_manager.prefix_digest(max_entries)

    def export_prefix_blocks(self, max_blocks: int = 64) -> List[tuple]:
        """Host copies of the hottest cached prefix blocks (the replica
        warm-up donor side) — see
        :meth:`DSStateManager.export_prefix_blocks`."""
        return self.state_manager.export_prefix_blocks(max_blocks)

    def import_prefix_blocks(self, entries: List[tuple]) -> int:
        """Seed the prefix cache with another replica's exported blocks
        (the warm-up receiver side) — see
        :meth:`DSStateManager.import_prefix_blocks`."""
        return self.state_manager.import_prefix_blocks(entries)

    def configure_prefix_cache(self, enabled: bool,
                               max_blocks: Optional[int] = None) -> None:
        """Toggle prefix caching on a built engine — the serving layer's
        config-driven hook (``ServingConfig.prefix_cache``). Enabling is
        safe at any time: matching/registration start from now (sequences
        already mid-flight are excluded from hashing by the chain-state
        consistency guard in ``record_tokens``). Disabling drops the whole
        index so retained blocks cannot strand outside the free pool."""
        sm = self.state_manager
        if enabled and sm.recurrent:
            sm.refuse_recurrent("the prefix cache")
        if enabled and len(sm.groups) > 1:
            sm.refuse_grouped("the prefix cache")
        self.config.enable_prefix_cache = bool(enabled)
        self.config.prefix_cache_max_blocks = max_blocks
        if enabled:
            sm.prefix_cache_enabled = True
            sm.prefix_cache_max_blocks = max_blocks or 0
        else:
            sm.clear_prefix_cache()
            sm.prefix_cache_enabled = False
            if sm.kv_tier_enabled:
                # the tier cannot outlive the cache it spills for
                self.configure_kv_tier(False)

    # ------------------------------------------------------------- KV tier
    def configure_kv_tier(self, enabled: bool,
                          host_bytes: Optional[int] = None,
                          disk_path: Optional[str] = None,
                          disk_bytes: Optional[int] = None) -> None:
        """Toggle the tiered KV spillover on a built engine — the serving
        layer's config-driven hook (``ServingConfig.kv_tier``; see
        docs/SERVING.md "KV tiering"). Enabling requires the prefix
        cache (spill/restore ride its eviction/match paths) and is safe
        at any time — spilling starts with the next eviction. Disabling
        drops every spilled entry (host and disk). ``None`` arguments
        keep the config's current values — re-tuning the host bound
        must not silently destroy a configured disk tier; pass
        ``disk_bytes=0`` to explicitly drop one."""
        host = (int(host_bytes) if host_bytes is not None
                else self.config.kv_tier_host_bytes)
        dpath = (disk_path if disk_path is not None
                 else self.config.kv_tier_disk_path)
        dbytes = (int(disk_bytes) if disk_bytes is not None
                  else self.config.kv_tier_disk_bytes)
        # build first, commit config after: a rejected configuration
        # (prefix cache off) must not leave config claiming a tier the
        # manager never built
        self.state_manager.configure_kv_tier(
            enabled, host_bytes=host, disk_path=dpath, disk_bytes=dbytes)
        self.config.kv_tier_enabled = bool(enabled)
        self.config.kv_tier_host_bytes = host
        self.config.kv_tier_disk_path = dpath
        self.config.kv_tier_disk_bytes = dbytes

    def tier_stats(self) -> Dict[str, int]:
        """Monotonic KV-tier counters (spilled/restored/dropped/...)
        plus current host/disk residency; all zeros (same shape) when no
        tier is configured — see :meth:`DSStateManager.tier_stats`."""
        return self.state_manager.tier_stats()

    def drain_restore_times(self) -> List[float]:
        """Restore-dispatch wall times since the last drain — the
        serving layer observes them into the ``kv_tier_restore_s``
        histogram."""
        return self.state_manager.drain_restore_times()

    def occupancy(self) -> Dict[str, int]:
        """KV-pool occupancy snapshot (blocks + bytes + evictable/
        available) — the single source the serving gauges
        (``kv_blocks_in_use``/``kv_bytes_in_use``) and the autoscaler
        read; see :meth:`DSStateManager.occupancy`."""
        return self.state_manager.occupancy()

    def configure_kv_quant(self, enabled: bool, dtype: str = "int8",
                           scale_granularity: str = "block") -> None:
        """Toggle int8 KV-cache quantization on a built engine — the
        serving layer's config-driven hook (``ServingConfig.kv_quant``).
        Unlike the prefix cache this re-allocates the KV pools (the
        representation changes), so it is only legal while no sequences
        are tracked: call it before traffic (the ``ServingFrontend``
        replica-build path) or after a drain."""
        if (bool(enabled) == self.state_manager.kv_quant
                and dtype == self.config.kv_quant_dtype
                and scale_granularity == self.config.kv_quant_scale_granularity):
            return
        if self.state_manager.tracked_sequences:
            raise RuntimeError(
                "cannot reconfigure kv_quant with "
                f"{len(self.state_manager.tracked_sequences)} sequences "
                "tracked — their KV blocks hold the old representation")
        if enabled:
            # validate BEFORE touching config: a rejected dtype must not
            # leave config claiming a representation the pools don't have
            from .kv_quant import validate_kv_quant

            validate_kv_quant(dtype, scale_granularity)
            if len(self.state_manager.groups) > 1:
                self.state_manager.refuse_grouped("quantized KV pools")
            if self.state_manager.headless:
                self.state_manager.refuse_latent("quantized KV pools")
        self.config.kv_quant_enabled = bool(enabled)
        self.config.kv_quant_dtype = dtype
        self.config.kv_quant_scale_granularity = scale_granularity
        self.state_manager = self._build_state_manager()
        self._compile_ahead()

    # ------------------------------------------------------- weight serving
    def configure_weight_quant(self, enabled: bool, dtype: str = "int8",
                               block: int = 128,
                               skip: Optional[Sequence[str]] = None) -> None:
        """Quantize this engine's weights in place — the serving layer's
        config-driven hook (``ServingConfig.weight_quant``; see
        docs/SERVING.md "Weight quantization"). Like ``configure_kv_quant``
        this is only legal before traffic (no tracked sequences): the
        compiled forward changes with the param pytree. Unlike KV pools,
        quantized weights cannot be un-quantized (the original values are
        gone — keeping a full-precision copy would defeat the byte cut),
        so disabling or re-coding an already-quantized engine raises:
        rebuild from the factory instead (what the frontend's replica
        paths do)."""
        skip_list = (list(skip) if skip is not None else [])
        already = self.config.weight_quant_enabled
        if already and enabled and dtype == self.config.weight_quant_dtype:
            # idempotent: an engine quantized at build meets the serving
            # config's apply with the same representation (block/skip
            # differences cannot be honored post-hoc — the full-precision
            # values are gone — and are advisory at this point)
            return
        if already:
            raise RuntimeError(
                "weights are already quantized "
                f"({self.config.weight_quant_dtype}) — quantization is "
                "lossy and cannot be reconfigured in place; rebuild the "
                "engine from its factory")
        if not enabled:
            return                      # off -> off: nothing to do
        if self.state_manager.tracked_sequences:
            raise RuntimeError(
                "cannot quantize weights with "
                f"{len(self.state_manager.tracked_sequences)} sequences "
                "tracked — mid-stream logits would shift under the "
                "requests' feet")
        from .weight_quant import quantize_weights

        self.params, self._weight_quant_stats = quantize_weights(
            self.model.cfg, split_qkv(self.model.cfg, self.params),
            dtype=dtype, block=int(block), skip=skip_list, tp=self.paged.tp)
        self.config.weight_quant_enabled = True
        self.config.weight_quant_dtype = dtype
        self.config.weight_quant_block = int(block)
        self.config.weight_quant_skip = skip_list
        self._compile_ahead()

    def param_stats(self) -> Dict[str, object]:
        """Resident param-byte accounting (total + quantized share) — the
        single source the ``param_bytes_total``/``param_bytes_quantized``
        serving gauges and the fabric status frames read; cheap (pure
        shape/dtype metadata, computed lazily once per param tree)."""
        if self._weight_quant_stats is None:
            from .weight_quant import param_stats

            self._weight_quant_stats = param_stats(
                self.params,
                dtype=(self.config.weight_quant_dtype
                       if self.config.weight_quant_enabled else ""),
                block=(self.config.weight_quant_block
                       if self.config.weight_quant_enabled else 0))
        return dict(self._weight_quant_stats)

    @property
    def free_blocks(self) -> int:
        return self.state_manager.free_blocks
