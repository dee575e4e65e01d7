"""TPU-native causal transformer LM (GPT-2 / Llama / Mistral families).

One configurable functional implementation replaces the reference's per-model
containers (``deepspeed/module_inject/containers/{gpt2,llama,opt,...}.py`` and
inference-v2 ``model_implementations/llama_v2/llama_v2_model.py:204``):

- pure-functional ``init`` / ``apply`` (no module system) so the whole train
  step is one jitted SPMD program;
- scan-over-layers with stacked layer params — O(1) compile time in depth and
  the natural substrate for pipeline parallelism (layer dim → ``pipe`` axis)
  and ``jax.checkpoint`` remat (the reference's activation checkpointing,
  ``runtime/activation_checkpointing/checkpointing.py:485``);
- every param carries a *logical* sharding spec consumed by
  ``parallel/sharding.py`` — Megatron-style TP (column QKV/MLP-in, row
  proj/MLP-out) falls out of the ``heads``/``mlp`` logical axes, ZeRO-3 out
  of the fsdp rule;
- GQA, RoPE, RMSNorm, SwiGLU for the Llama/Mistral family; learned positions,
  LayerNorm, GELU for GPT-2.

Attention dispatches to the Pallas flash-attention kernel on TPU
(``deepspeed_tpu/ops/flash_attention.py``) and a pure-XLA reference path
elsewhere — the counterpart of the reference's fused CUDA transformer kernels
(``csrc/transformer/``).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..parallel.sharding import spec


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 50257
    hidden_size: int = 768
    intermediate_size: int = 3072
    num_layers: int = 12
    num_heads: int = 12
    num_kv_heads: Optional[int] = None   # GQA; None => MHA
    max_seq_len: int = 1024
    # Sliding-window attention (Mistral/Qwen2). Either one global window
    # (int) or a per-layer tuple of length num_layers (None/0 entries =
    # full attention) — Qwen2's mixed schedule ("the first
    # max_window_layers layers use full attention", HF configuration_
    # qwen2.py; reference plumb-through: inference/v2/model_
    # implementations/mistral/model.py:202). Per-layer windows compile
    # one lax.scan per contiguous constant-window run (see
    # window_segments), so schedules with few transitions stay O(1) in
    # depth.
    sliding_window: Optional[Any] = None  # int | tuple[Optional[int], ...]
    # architecture switches
    norm: str = "layernorm"              # "layernorm" | "rmsnorm"
    activation: str = "gelu"             # "gelu" | "silu" (SwiGLU) | "relu"
    position: str = "learned"            # "learned" | "rope" | "alibi"
    rope_theta: float = 10000.0
    rope_pct: float = 1.0                # partial rotary (GPT-NeoX rotary_pct)
    rope_interleaved: bool = False       # GPT-J rotate_every_two pair layout
    tie_embeddings: bool = True
    norm_eps: float = 1e-5
    use_bias: bool = False               # linear biases (GPT-2/OPT style)
    qkv_bias: bool = False               # biases on q/k/v only (Qwen2)
    o_bias: Optional[bool] = None        # attn out-proj bias; None → use_bias
    attn_scale: Optional[float] = None   # softmax scale; None → 1/√head_dim
    #   (GPT-Neo trains UNSCALED attention — scale 1.0 — folding the
    #   normalization into its init; HF GPTNeoSelfAttention matmuls q·kᵀ
    #   raw, so parity requires the override)
    mlp_bias: Optional[bool] = None      # MLP biases; None → use_bias (GPT-J)
    lm_head_bias: bool = False           # bias on the LM head (GPT-J)
    parallel_residual: bool = False      # x + attn(ln1 x) + mlp(ln2 x) (NeoX/Falcon)
    shared_layernorm: bool = False       # parallel residual reads ONE ln (GPT-J)
    embedding_layernorm: bool = False    # LayerNorm after wte (BLOOM)
    dropout: float = 0.0
    dtype: Any = jnp.float32             # compute dtype (params kept fp32)
    remat: bool = False                  # activation checkpointing per layer
    remat_policy: Optional[str] = None   # None|"dots_saveable"|"nothing_saveable"
    use_flash_attention: bool = True     # pallas kernel on TPU
    flash_block_q: int = 1024     # 1024/1024 measured fastest on v5e
    flash_block_kv: int = 1024    # (52.5 vs 36.2 TF/s fwd+bwd at 512/512)
    attention_impl: str = "flash"        # "flash" | "reference" | "ring" | "sparse"
    # block-sparse attention (ops/sparse_attention.py) when attention_impl
    # == "sparse": pattern + its knobs (reference ops/sparse_attention
    # sparsity_config.py surface)
    sparse_pattern: str = "fixed"        # fixed | bigbird | bslongformer | variable
    sparse_block: int = 64
    sparse_num_local_blocks: int = 4
    sparse_num_global_blocks: int = 1
    sparse_num_random_blocks: int = 1
    sparse_num_sliding_window_blocks: int = 3
    pipeline_microbatches: int = 0       # 0 → pipe-axis size when pipelined
    # MoE (reference deepspeed/moe/): >0 turns every MLP into a top-k MoE
    moe_num_experts: int = 0
    moe_top_k: int = 1
    moe_capacity_factor: float = 1.25
    moe_min_capacity: int = 4
    moe_aux_loss_coef: float = 0.01
    moe_dropless: bool = False   # ragged_dot grouped GEMM (moe/grouped.py)
    # Hybrid blocks (models/hybrid.py, the kinds under models/mixers/):
    # layers of more than one kind.
    # ``layer_pattern`` is one period of mixer kinds ("full" | "linear" |
    # "window": attention over the last ``sliding_window`` positions |
    # "latent": attention whose cache is one latent a token, below),
    # repeated (num_layers - len(lead_layers)) / len(pattern) times and
    # scanned one period an iteration; None is the uniform attention
    # block above. A position that is None has no mixer: an FFN alone
    # (``layer_ffn``). The fields below are read by the hybrid block only.
    layer_pattern: Optional[Tuple[Optional[str], ...]] = None
    # mixer kinds of the layers run before the scan, each followed by a
    # dense MLP of ``intermediate_size`` instead of the sparse FFN
    lead_layers: Tuple[str, ...] = ()
    # which positions of the period carry an FFN (None: every one). A
    # position may be a mixer alone (False here) or an FFN alone (None in
    # ``layer_pattern``): one norm and one residual add, a layer of a
    # model that counts its mixers and its FFNs as layers of their own
    layer_ffn: Optional[Tuple[bool, ...]] = None
    # A model that is several runs of layers, each with a pattern and a
    # period count of its own: ``((pattern, periods), ...)``, run after
    # run, each a scan of its own (a decoder-hybrid-decoder is
    # ``(("mamba1", "window"), 8), (("mamba1", "full"), 1), (("gmu",
    # "cross"), 7)``). ``layer_pattern`` is then the runs' patterns laid
    # end to end (set from this; given, it has to agree), every position
    # is a mixer with the dense MLP behind it, and there are no
    # ``lead_layers``. Values may ride beside the residual stream from a
    # run to the runs behind it (``mixers.base.Mixer.hands`` / ``takes``:
    # ``run_feeds``), and a serving forward's rows may leave behind the
    # last layer that writes a cache (``exit_at``).
    layer_runs: Optional[Tuple[Tuple[Tuple[str, ...], int], ...]] = None
    # the differential form of the "full" / "window" / "cross" kinds
    # (models/mixers/attention.py): heads in pairs, two softmaxes a pair
    diff_attn: bool = False
    head_size: Optional[int] = None      # stated head size; None → hidden/heads
    attn_output_gate: bool = False       # wq twice as wide: [q | gate] a head
    attn_gate_proj: bool = False         # ... or a projection of its own (wg)
    # attention kinds whose q and k are rotated; None: all of them. A
    # kind left out has no position term at all
    rope_kinds: Optional[Tuple[str, ...]] = None
    sandwich_norm: bool = False          # a norm after the mixer and the FFN too
    embed_scale: float = 1.0             # the embedding's multiplier
    qk_norm: bool = False                # RMSNorm over each head of q and k
    norm_zero_centered: bool = False     # RMSNorm gain is 1 + w
    linear_num_key_heads: int = 0        # Gated DeltaNet layer sizes
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel: int = 4
    moe_norm_topk: bool = False          # top-k weights divided by their sum
    moe_score_func: str = "softmax"      # | "sigmoid": each expert's own score
    moe_select_bias: bool = False        # top-k over score + a learned bias
    #   (``router_b``); the weights stay the unbiased scores
    moe_route_scale: float = 1.0         # the top-k weights' multiplier
    moe_shared_gate: bool = True         # shared expert under a sigmoid gate
    moe_held_experts: Optional[Tuple[int, int]] = None  # (lo, n): the share
    #   of the moe_num_experts routed over whose weights this model holds
    moe_intermediate_size: Optional[int] = None   # None → intermediate_size
    moe_shared_intermediate_size: int = 0         # shared expert; 0 = none
    # the experts' (and the shared expert's) form: "silu" and "reglu" are
    # the gated ones (``down(act(gate(x)) * up(x))``, three matrices an
    # expert: act is SiLU, or ReLU for "reglu"), "relu2" the ungated
    # ``down(relu(up(x))²)``, two. (``activation`` "relu" above, a dense
    # model's ungated MLP, is another thing.)
    moe_activation: str = "silu"
    # where the sparse FFN's router reads: "ffn" — the FFN's own normed
    # input, behind the mixer's residual add — or "layer": the layer's
    # input as it comes in, ahead of the mixer's norm (the logits exist
    # before attention and are handed to the FFN two sub-layers later).
    # "layer" asks a mixer and an FFN of every position of the period.
    moe_router_input: str = "ffn"
    # > 0: the routed experts run in a latent this wide, between two
    # projections every token passes once (``latent_w_in`` before the
    # dispatch, ``latent_w_out`` behind the combine); the router and the
    # shared expert stay on the full width
    moe_latent_size: int = 0
    # Latent attention (a "latent" layer, models/mixers/latent.py): queries
    # through a rank-``q_lora_rank`` bottleneck, keys and values rebuilt
    # from a rank-``kv_lora_rank`` latent a token; a head's query and key
    # are ``[nope | rope]`` wide (only the rope part is rotated, and the
    # key's is one for all heads), its value ``v_head_dim``. The cache is
    # the latent and the rotated key part: ``latent_dim`` numbers a token
    # a layer, whatever the head count.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # A "latent_sparse" layer is that layer behind a learned selection:
    # an indexer of ``index_n_heads`` heads ``index_head_dim`` wide scores
    # every earlier position, and the layer attends the ``index_topk`` of
    # largest score only (all of them while the context is shorter). Its
    # cache is the latent row and, beside it, the indexer's key.
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # A "latent_window" layer is latent attention over the last
    # ``sliding_window`` positions, at sizes of its own (``swa_*``: heads,
    # ranks, head widths, rope base); its cache is a layer group of its
    # own, whose blocks behind the window go back.
    swa_num_heads: int = 0
    swa_q_lora_rank: int = 0
    swa_kv_lora_rank: int = 0
    swa_qk_nope_head_dim: int = 0
    swa_qk_rope_head_dim: int = 0
    swa_v_head_dim: int = 0
    swa_rope_theta: float = 0.0
    attn_gate_headwise: bool = False     # latent kinds: one sigmoid gate a
    #   head on the attention output, from ``w_g`` (hidden -> heads)
    latent_rescale: bool = False         # c_q and c times sqrt(hidden /
    #   rank) behind their norms
    # A "lightning" layer (ops/lightning_attention.py): linear attention
    # under one scalar decay a head, ``lightning_num_heads`` heads of
    # ``lightning_head_dim`` on both sides of a float32 state a sequence;
    # q/k RMSNorm a head (``qk_norm``), an RMSNorm over the joined heads'
    # output and a sigmoid gate of the layer's input in front of ``wo``.
    lightning_num_heads: int = 0
    lightning_head_dim: int = 0
    # A "mamba2" layer (ops/mamba2_ssd.py): the Mamba-2 state-space layer,
    # ``mamba_num_heads`` heads of ``mamba_head_dim`` channels over a
    # float32 state ``[heads, head_dim, mamba_state_size]`` a sequence
    # under a decay that depends on the token, B and C shared by the
    # heads of one of ``mamba_n_groups`` groups, a depthwise causal conv
    # of ``mamba_conv_kernel`` taps (with bias) in front and a gated
    # RMSNorm by group behind; chunks of ``mamba_chunk_size`` tokens in
    # the chunked form.
    mamba_num_heads: int = 0
    mamba_head_dim: int = 0
    mamba_state_size: int = 0
    mamba_n_groups: int = 1
    mamba_conv_kernel: int = 4
    mamba_chunk_size: int = 128
    # A "mamba1" layer (ops/selective_scan.py): the Mamba (S6)
    # state-space layer, ``mamba1_inner_size`` channels over a float32
    # state ``[mamba1_state_size, channels]`` a sequence under a decay of
    # its own a channel and a state index, its step from a rank-
    # ``mamba1_dt_rank`` projection, a depthwise causal conv of
    # ``mamba1_conv_kernel`` taps (with bias) in front and a gate behind. A
    # "gmu" layer gates the memory such a layer hands on, as wide.
    # ``mamba1_inner_norm`` (Jamba): an RMSNorm with a gain over each of
    # the step's projection, B and C, between ``W_x`` and ``W_dt``.
    mamba1_inner_size: int = 0
    mamba1_state_size: int = 0
    mamba1_dt_rank: int = 0
    mamba1_conv_kernel: int = 4
    mamba1_inner_norm: bool = False
    # A "block_sparse" layer (InfLLM-V2): a "full" layer whose query, from
    # position ``block_dense_len`` on, attends whole blocks of
    # ``block_select_size`` keys only: the first ``block_init_blocks``,
    # those that reach into its last ``block_window`` positions, and the
    # ``block_topk`` others of largest score — a K/V head's queries
    # scored together against the means of ``block_kernel_size`` keys
    # taken every ``block_kernel_stride`` (no weights:
    # models/mixers/block_sparse.py ``block_*``). In serving the block is
    # the pool's.
    block_kernel_size: int = 0
    block_kernel_stride: int = 0
    block_select_size: int = 0
    block_topk: int = 0
    block_init_blocks: int = 1
    block_window: int = 0
    block_dense_len: int = 0
    # muP's multipliers (MiniCPM): each mixer's and each FFN's output
    # before its residual add, and the logits. 1.0 is no multiply at all.
    residual_scale: float = 1.0
    logit_scale: float = 1.0

    def __post_init__(self):
        # a configuration read from JSON brings lists
        for name in ("layer_pattern", "moe_held_experts", "lead_layers",
                     "rope_kinds", "layer_ffn"):
            value = getattr(self, name)
            if isinstance(value, list):
                object.__setattr__(self, name, tuple(value))
        if self.layer_runs is not None:
            self._check_runs()
        if self.layer_pattern is not None:
            from .mixers import KINDS, kinds_of

            pattern, lead = self.layer_pattern, self.lead_layers
            ffn = self.layer_ffn or (True,) * len(pattern or ())
            if not pattern or any(k not in KINDS for k in lead) \
                    or any(k is not None and k not in KINDS for k in pattern) \
                    or (self.layer_runs is None and (
                        (self.num_layers - len(lead)) % len(pattern)
                        or self.num_layers <= len(lead))):
                raise ValueError(
                    f"layer_pattern {pattern!r}: a period of "
                    f"{tuple(KINDS)} that divides num_layers "
                    f"({self.num_layers}) less the {len(lead)} lead_layers")
            if len(ffn) != len(pattern) or any(
                    k is None and not f for k, f in zip(pattern, ffn)):
                raise ValueError(
                    f"layer_ffn {self.layer_ffn!r}: one entry a position "
                    f"of layer_pattern {pattern!r}, and every position a "
                    "mixer, an FFN or both")
            if self.moe_activation not in ("silu", "reglu", "relu2") or (
                    (self.moe_activation != "silu" or self.moe_latent_size)
                    and (lead or self.moe_num_experts <= 0)):
                raise ValueError(
                    "moe_activation is \"silu\" or \"reglu\" (gated: SiLU "
                    "or ReLU of the gate) or \"relu2\" (ungated); it and "
                    "moe_latent_size are the sparse FFN's (the dense MLP "
                    "of lead_layers or of moe_num_experts 0 is SwiGLU)")
            if self.moe_router_input not in ("ffn", "layer") or (
                    self.moe_router_input == "layer"
                    and (self.moe_num_experts <= 0 or None in pattern
                         or not all(ffn))):
                raise ValueError(
                    "moe_router_input is \"ffn\" or \"layer\"; \"layer\" "
                    "takes the router's logits from the input of a layer "
                    "that has both a mixer and a sparse FFN, so every "
                    "position of layer_pattern is a mixer (none is null), "
                    "layer_ffn leaves none out and moe_num_experts > 0")
            kinds = kinds_of(self)
            for kind in kinds:
                KINDS[kind].check(self)
            windowed = any(KINDS[kind].windowed for kind in kinds)
            if windowed != (isinstance(self.sliding_window, int)
                            and self.sliding_window > 0) \
                    or self.moe_num_experts < 0 \
                    or self.norm not in ("rmsnorm", "layernorm") \
                    or self.position != "rope":
                raise ValueError(
                    "a hybrid block is RMSNorm or LayerNorm and rotary "
                    "(rope_kinds names the kinds that are), its FFN the "
                    "sparse one (moe_num_experts > 0) or the dense MLP "
                    "(0); sliding_window is its \"window\" layers' "
                    "length, and set with them only")
            if self.layer_runs is not None:
                self.run_feeds()        # raises on a layer nothing feeds
            elif any(KINDS[kind].takes for kind in kinds):
                raise ValueError(
                    f"the kinds {[k for k in kinds if KINDS[k].takes]} read "
                    "what an earlier run of layers hands on: they belong "
                    "to layer_runs")
        elif self.lead_layers or self.moe_router_input != "ffn":
            raise ValueError("lead_layers and moe_router_input belong to a "
                             "layer_pattern")

    def _check_runs(self):
        """``layer_runs`` made tuples and held to its rules; sets
        ``layer_pattern`` to the runs' patterns end to end."""
        try:
            runs = tuple((tuple(pattern), int(n))
                         for pattern, n in self.layer_runs)
        except (TypeError, ValueError):
            runs = ()
        flat = tuple(kind for pattern, _ in runs for kind in pattern)
        if not runs or any(not pattern or n < 1 for pattern, n in runs) \
                or None in flat \
                or sum(len(pattern) * n for pattern, n in runs) \
                != self.num_layers \
                or self.lead_layers or self.layer_ffn is not None \
                or self.moe_num_experts \
                or self.layer_pattern not in (None, flat):
            raise ValueError(
                f"layer_runs {self.layer_runs!r}: ((pattern, periods), "
                f"...), every position a mixer kind, whose layers add up "
                f"to num_layers ({self.num_layers}); no lead_layers, no "
                "layer_ffn, no experts, and a layer_pattern (if any) that "
                "is the runs' patterns end to end")
        object.__setattr__(self, "layer_runs", runs)
        object.__setattr__(self, "layer_pattern", flat)

    @property
    def runs(self) -> Tuple[Tuple[Tuple[Optional[str], ...], int], ...]:
        """A hybrid block's runs of layers behind its lead layers:
        ``layer_runs``, or the one pattern with its periods."""
        return self.layer_runs or ((self.layer_pattern, self.num_periods),)

    def run_feeds(self, cached: bool = False) -> Tuple[Tuple[str, ...], ...]:
        """For each run of ``layer_runs``, the values its kinds hand on
        beside the residual stream (``Mixer.hands``) that a later run's
        kinds take (``Mixer.takes``): a value comes from the latest
        earlier run that can hand it, and that run is one period (its
        layers are laid out inline, and what the last of them hands is
        what rides on). ``cached``: serving, where a kind that ``shares``
        another's pool rows takes nothing through the carry. Raises
        ``ValueError`` on a layer that no earlier run feeds."""
        from .mixers import KINDS

        feeds = [set() for _ in self.layer_runs]
        for t, (pattern, _) in enumerate(self.layer_runs):
            for kind in dict.fromkeys(pattern):
                for name in KINDS[kind].takes:
                    source = max((s for s in range(t) if any(
                        name in KINDS[k].hands
                        for k in self.layer_runs[s][0])), default=None)
                    if source is None or self.layer_runs[source][1] != 1:
                        raise ValueError(
                            f"a {kind!r} layer of run {t} reads {name!r}, "
                            "which no earlier run of one period hands on "
                            f"(layer_runs {self.layer_runs!r})")
                    if not (cached and KINDS[kind].shares):
                        feeds[source].add(name)
        return tuple(tuple(sorted(names)) for names in feeds)

    def exit_at(self) -> Optional[Tuple[int, int]]:
        """``(run, position)`` of the last layer that writes a cache or a
        state, where a serving forward's rows may leave but for their
        last valid position — behind it a position's value depends on
        that position's own ``x`` and on caches alone, and the engine
        reads a row's last. None where there is no such exit: not a model
        of ``layer_runs``, the layer in a scanned run, of a kind that
        cannot (``Mixer.exits``) or one that is rotated."""
        if self.layer_runs is None:
            return None
        from .mixers import KINDS

        writers = [(r, i) for r, (pattern, _) in enumerate(self.layer_runs)
                   for i, kind in enumerate(pattern)
                   if KINDS[kind].pool is not None
                   or KINDS[kind].state is not None]
        if not writers:
            return None
        r, i = writers[-1]
        pattern, periods = self.layer_runs[r]
        rotated = self.rope_kinds is None or pattern[i] in self.rope_kinds
        if periods != 1 or not KINDS[pattern[i]].exits or rotated:
            return None
        return r, i

    def paged_heads(self) -> Tuple[int, int, int]:
        """(heads, K/V heads, head size) as the paged attention and its
        pool see a hybrid block's attention layers: the model's, or under
        ``diff_attn`` the joined pairs' (models/mixers/attention.py)."""
        if self.diff_attn:
            return self.num_heads, self.kv_heads // 2, 2 * self.head_dim
        return self.num_heads, self.kv_heads, self.head_dim

    @property
    def head_dim(self) -> int:
        """A head's query-key width (the softmax scale's default root)."""
        if self.is_latent:
            return self.qk_nope_head_dim + self.qk_rope_head_dim
        return self.head_size or self.hidden_size // self.num_heads

    @property
    def is_latent(self) -> bool:
        """Whether the attention layers are latent ones (their cache has
        no head axis: ``kv_layouts``)."""
        from .mixers import KINDS, kinds_of

        return any(KINDS[kind].headless for kind in kinds_of(self))

    def latent_sizes(self, kind: str = "latent"):
        """The sizes of one latent kind's layers
        (``mixers.latent.LatentSizes``)."""
        from .mixers.latent import sizes

        return sizes(self, kind)

    @property
    def latent_dim(self) -> int:
        """What a whole-context latent layer caches a token: the latent
        and the rotated key part (``latent_sizes(kind).dim`` of any)."""
        return self.latent_sizes().dim

    @property
    def latent_width(self) -> int:
        """A token's row in the whole-context latent pool: ``latent_dim``
        padded with zeros to whole 128-lane tiles, which is what the row
        occupies on the chip whether the pad is stated or not."""
        return self.latent_sizes().width

    @property
    def is_hybrid(self) -> bool:
        return self.layer_pattern is not None

    @property
    def num_periods(self) -> int:
        """The periods of the one pattern (a model of ``layer_runs`` has
        a count a run: ``runs``)."""
        if self.layer_runs is not None:
            raise AttributeError("a model of layer_runs has no one "
                                 "num_periods: read cfg.runs")
        return (self.num_layers - len(self.lead_layers)) \
            // len(self.layer_pattern)

    def layers_of(self, kind: str) -> int:
        """A hybrid block's layers of one mixer kind, lead layers and
        periods together."""
        return self.lead_layers.count(kind) + sum(
            n * pattern.count(kind) for pattern, n in self.runs)

    def ffn_at(self, i: int) -> bool:
        """Whether position ``i`` of the period carries an FFN."""
        return self.layer_ffn is None or bool(self.layer_ffn[i])

    @property
    def num_attn_layers(self) -> int:
        """Layers that keep per-token K/V (all of them, unless hybrid)."""
        if self.layer_pattern is None:
            return self.num_layers
        from .mixers import KINDS, kinds_of

        return sum(self.layers_of(kind) for kind in kinds_of(self)
                   if KINDS[kind].pool is not None)

    @property
    def num_linear_layers(self) -> int:
        """Layers that keep a recurrent state instead."""
        from .mixers import KINDS, kinds_of

        return sum(self.layers_of(kind) for kind in kinds_of(self)
                   if KINDS[kind].state is not None)

    @property
    def num_sparse_layers(self) -> int:
        """Layers whose FFN is the sparse one."""
        if self.moe_num_experts <= 0:
            return 0
        if self.layer_ffn is not None:
            return self.num_periods * sum(map(bool, self.layer_ffn))
        return self.num_layers - len(self.lead_layers)

    def kv_groups(self) -> Tuple[Tuple[int, int], ...]:
        """``(window, layers)`` of each group of layers whose K/V has one
        lifetime, and so a pool and a block table of its own in serving
        (inference/v2/ragged/manager.py): 0 = the whole context. The
        group whose K/V lives longest comes first. A uniform model is one
        group; so is a dense model whose layers differ in window (its
        layers share one stacked pool: nothing is released there)."""
        if self.layer_pattern is None:
            sw = self.sliding_window
            return ((int(sw) if isinstance(sw, int) else 0,
                     self.num_layers),)
        from .mixers import KINDS, kinds_of

        layers = [0, 0]         # the whole context's, the window's
        for kind in kinds_of(self):
            if KINDS[kind].pool is not None:
                layers[KINDS[kind].windowed] += self.layers_of(kind)
        groups = [(0, layers[0]),
                  (int(self.sliding_window or 0), layers[1])]
        return tuple(g for g in groups if g[1])

    def kv_layouts(self, block_size: int) -> Tuple[Dict[str, Tuple[int,
                                                                    ...]],
                                                   ...]:
        """Each layer group's pool, in ``kv_groups``' order: its leaves
        and one block's shape in each — ``k`` and ``v`` ``[KH, bs, D]``
        for a uniform model, and for a hybrid block what the group's
        kinds state (``mixers.base.Mixer.pool``)."""
        if self.layer_pattern is None:
            block = (self.kv_heads, block_size, self.head_dim)
            return ({"k": block, "v": block},)
        from .mixers import KINDS, kinds_of

        layouts = []
        for window, _ in self.kv_groups():
            leaves = {}
            for kind in kinds_of(self):
                if KINDS[kind].pool is not None \
                        and KINDS[kind].windowed == bool(window):
                    leaves.update(KINDS[kind].pool(self, block_size))
            layouts.append(leaves)
        return tuple(layouts)

    def kv_layout(self, block_size: int) -> Tuple[Tuple[str, ...],
                                                  Tuple[int, ...]]:
        """The first group's leaves and its first leaf's block: the
        whole of it for a model whose groups share one layout and whose
        leaves share one block shape (``kv_layouts`` says the rest)."""
        first = self.kv_layouts(block_size)[0]
        return tuple(first), next(iter(first.values()))

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def resolved_o_bias(self) -> bool:
        """Attention out-proj bias (o_bias overrides; None → use_bias)."""
        return self.use_bias if self.o_bias is None else self.o_bias

    @property
    def rot_dim(self) -> int:
        """Rotary dims per head (even; < head_dim for partial rotary)."""
        if self.is_latent:
            return self.qk_rope_head_dim
        return int(self.head_dim * self.rope_pct) // 2 * 2

    def layer_windows(self) -> Tuple[int, ...]:
        """Per-layer sliding windows, length num_layers; 0 = full
        attention. A scalar ``sliding_window`` broadcasts to all layers."""
        sw = self.sliding_window
        if sw is None or isinstance(sw, int):
            return (int(sw or 0),) * self.num_layers
        if len(sw) != self.num_layers:
            raise ValueError(
                f"sliding_window tuple has {len(sw)} entries for "
                f"{self.num_layers} layers")
        return tuple(int(w or 0) for w in sw)

    def window_segments(self) -> Tuple[Tuple[int, int, int], ...]:
        """Contiguous (start, length, window) runs of equal window over
        the layer dim. Each run scans separately (the Pallas kernels take
        the window statically — it prunes the KV grid), so a schedule
        with R transitions costs R compiled block bodies. Qwen2's
        full-then-SWA schedule is R=2; uniform windows stay R=1."""
        ws = self.layer_windows()
        segs = []
        start = 0
        for i in range(1, len(ws) + 1):
            if i == len(ws) or ws[i] != ws[start]:
                segs.append((start, i - start, ws[start]))
                start = i
        return tuple(segs)

    def num_params(self) -> int:
        h, m, v, L = self.hidden_size, self.intermediate_size, self.vocab_size, self.num_layers
        kvh = self.kv_heads * self.head_dim
        attn = h * h + 2 * h * kvh + h * h                 # q, k, v, o
        mlp = (3 if self.activation == "silu" else 2) * h * m
        if self.moe_num_experts > 0:
            mlp = mlp * self.moe_num_experts + h * self.moe_num_experts  # experts + router
        norms = (2 if self.norm == "rmsnorm" else 4) * h
        per_layer = attn + mlp + norms
        emb = v * h + (self.max_seq_len * h if self.position == "learned" else 0)
        head = 0 if self.tie_embeddings else v * h
        return L * per_layer + emb + head + h


# Registered configurations (sizes follow the public model cards).
GPT2_125M = TransformerConfig()
LLAMA2_7B = TransformerConfig(vocab_size=32000, hidden_size=4096,
                              intermediate_size=11008, num_layers=32,
                              num_heads=32, num_kv_heads=32, max_seq_len=4096,
                              norm="rmsnorm", activation="silu",
                              position="rope", tie_embeddings=False,
                              norm_eps=1e-5, dtype=jnp.bfloat16)
LLAMA2_70B = TransformerConfig(vocab_size=32000, hidden_size=8192,
                               intermediate_size=28672, num_layers=80,
                               num_heads=64, num_kv_heads=8, max_seq_len=4096,
                               norm="rmsnorm", activation="silu",
                               position="rope", tie_embeddings=False,
                               dtype=jnp.bfloat16)
MISTRAL_7B = TransformerConfig(vocab_size=32000, hidden_size=4096,
                               intermediate_size=14336, num_layers=32,
                               num_heads=32, num_kv_heads=8, max_seq_len=8192,
                               norm="rmsnorm", activation="silu",
                               position="rope", tie_embeddings=False,
                               rope_theta=10000.0, sliding_window=4096,
                               dtype=jnp.bfloat16)
QWEN2_7B = TransformerConfig(vocab_size=152064, hidden_size=3584,
                             intermediate_size=18944, num_layers=28,
                             num_heads=28, num_kv_heads=4, max_seq_len=32768,
                             norm="rmsnorm", activation="silu",
                             position="rope", rope_theta=1e6,
                             tie_embeddings=False, qkv_bias=True,
                             norm_eps=1e-6, dtype=jnp.bfloat16)
OPT_1B3 = TransformerConfig(vocab_size=50272, hidden_size=2048,
                            intermediate_size=8192, num_layers=24,
                            num_heads=32, max_seq_len=2048,
                            norm="layernorm", activation="relu",
                            position="learned", tie_embeddings=True,
                            use_bias=True, dtype=jnp.bfloat16)
GPTJ_6B = TransformerConfig(vocab_size=50400, hidden_size=4096,
                            intermediate_size=16384, num_layers=28,
                            num_heads=16, max_seq_len=2048,
                            norm="layernorm", activation="gelu",
                            position="rope", rope_pct=0.25,
                            rope_interleaved=True, parallel_residual=True,
                            shared_layernorm=True, tie_embeddings=False,
                            mlp_bias=True, lm_head_bias=True,
                            dtype=jnp.bfloat16)
PHI_2 = TransformerConfig(vocab_size=51200, hidden_size=2560,
                          intermediate_size=10240, num_layers=32,
                          num_heads=32, max_seq_len=2048,
                          norm="layernorm", activation="gelu",
                          position="rope", rope_pct=0.4,
                          parallel_residual=True, shared_layernorm=True,
                          tie_embeddings=False, use_bias=True,
                          mlp_bias=True, lm_head_bias=True,
                          dtype=jnp.bfloat16)
PYTHIA_1B4 = TransformerConfig(vocab_size=50304, hidden_size=2048,
                               intermediate_size=8192, num_layers=24,
                               num_heads=16, max_seq_len=2048,
                               norm="layernorm", activation="gelu_exact",
                               position="rope", rope_pct=0.25,
                               parallel_residual=True, tie_embeddings=False,
                               use_bias=True, dtype=jnp.bfloat16)
BLOOM_560M = TransformerConfig(vocab_size=250880, hidden_size=1024,
                               intermediate_size=4096, num_layers=24,
                               num_heads=16, max_seq_len=2048,
                               norm="layernorm", activation="gelu",
                               position="alibi", embedding_layernorm=True,
                               tie_embeddings=True, use_bias=True,
                               dtype=jnp.bfloat16)
FALCON_7B = TransformerConfig(vocab_size=65024, hidden_size=4544,
                              intermediate_size=18176, num_layers=32,
                              num_heads=71, num_kv_heads=1, max_seq_len=2048,
                              norm="layernorm", activation="gelu_exact",
                              position="rope", parallel_residual=True,
                              tie_embeddings=True, dtype=jnp.bfloat16)
TINY_TEST = TransformerConfig(vocab_size=256, hidden_size=64,
                              intermediate_size=128, num_layers=2,
                              num_heads=4, num_kv_heads=2, max_seq_len=128,
                              norm="rmsnorm", activation="silu",
                              position="rope", tie_embeddings=True)


# ------------------------------------------------------------------ primitives

def _linear(x, w, b, dt):
    """x @ w (+ b) in compute dtype; b may be None (bias-free families).

    ``w`` may be a blockwise-quantized ``{"qw", "qs"}`` node
    (int8/fp8 weight serving — inference/v2/weight_quant.py): the matmul
    then runs straight from the quantized representation through
    ``ops/quantizer.quantized_matmul`` (dequantize-in-kernel on the
    Pallas path, fused dequant-then-dot on XLA, fp32 accumulation). An
    array weight takes the historical path byte for byte — the dispatch
    is on pytree structure at trace time, so the unquantized program is
    untouched."""
    if isinstance(w, dict):
        from ..ops.quantizer import quantized_matmul

        y = quantized_matmul(x, w["qw"], w["qs"], out_dtype=dt)
    else:
        y = x @ w.astype(dt)
    return y if b is None else y + b.astype(dt)


def _norm(x, w, b, kind: str, eps: float):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    if kind == "rmsnorm":
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        y = x32 * lax.rsqrt(var + eps) * w.astype(jnp.float32)
    else:
        mu = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
        y = (x32 - mu) * lax.rsqrt(var + eps) * w.astype(jnp.float32) + b.astype(jnp.float32)
    return y.astype(dt)


def rope_table(max_len: int, head_dim: int, theta: float) -> Tuple[jnp.ndarray, jnp.ndarray]:
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(max_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv)                   # [T, D/2]
    return jnp.cos(freqs), jnp.sin(freqs)


def apply_rope(x, cos, sin, interleaved: bool = False):
    """x: [B, T, H, D]; cos/sin: [T, R/2] (shared positions) or [B, T, R/2]
    (per-sequence positions — the ragged decode path), with R ≤ D (partial
    rotary — the GPT-NeoX rotary_pct layout leaves the trailing D−R dims
    unrotated). ``interleaved``: GPT-J's rotate_every_two pair layout
    (pairs are (0,1),(2,3),… instead of the rotate_half (i, i+R/2) split).
    """
    rot = cos.shape[-1] * 2
    xr, x_pass = x[..., :rot], x[..., rot:]
    if interleaved:
        x1, x2 = xr[..., 0::2], xr[..., 1::2]
    else:
        x1, x2 = jnp.split(xr, 2, axis=-1)
    if cos.ndim == 3:
        c = cos[:, :, None, :]
        s = sin[:, :, None, :]
    else:
        c = cos[None, :, None, :]
        s = sin[None, :, None, :]
    r1 = x1 * c - x2 * s
    r2 = x2 * c + x1 * s
    if interleaved:
        out = jnp.stack([r1, r2], axis=-1).reshape(xr.shape)
    else:
        out = jnp.concatenate([r1, r2], axis=-1)
    if x_pass.shape[-1]:
        out = jnp.concatenate([out, x_pass], axis=-1)
    return out.astype(x.dtype)


def alibi_slopes(num_heads: int) -> jnp.ndarray:
    """Per-head ALiBi slopes (Press et al.; the reference's softmax kernel
    bakes these in — csrc/transformer/inference/csrc/softmax.cu alibi path)."""
    m = 2 ** math.floor(math.log2(num_heads))
    base = [2.0 ** (-8.0 * (i + 1) / m) for i in range(m)]
    if m < num_heads:
        extra = [2.0 ** (-4.0 * (2 * i + 1) / m) for i in range(num_heads - m)]
        base += extra
    return jnp.asarray(base, jnp.float32)


def attention_reference(q, k, v, causal: bool = True, mask=None, bias=None,
                        window: int = 0, scale=None):
    """Pure-XLA attention: q [B,T,H,D], k/v [B,S,KH,D].

    GQA is expressed as an einsum over the [KH, group] head factorization —
    no ``jnp.repeat``, so K/V are never copied in HBM. ``bias``: optional
    additive [H, S] logit bias (ALiBi — per-row-constant terms cancel in
    softmax, so slopes·key_position suffices). ``window`` > 0: sliding
    window (query p attends keys in (p − window, p]).
    """
    B, T, H, D = q.shape
    S, KH = k.shape[1], k.shape[2]
    group = H // KH
    scale = 1.0 / math.sqrt(D) if scale is None else float(scale)
    qg = q.reshape(B, T, KH, group, D)
    logits = jnp.einsum("btkgd,bskd->bkgts", qg, k).astype(jnp.float32) * scale
    if bias is not None:
        logits = logits + bias.reshape(KH, group, 1, S)[None]
    if window and not causal:
        raise ValueError("sliding window requires causal attention")
    if causal:
        qpos = jnp.arange(T)[:, None] + (S - T)
        kpos = jnp.arange(S)[None, :]
        cmask = qpos >= kpos
        if window:
            cmask = cmask & (qpos - kpos < window)
        logits = jnp.where(cmask[None, None, None], logits, -1e30)
    if mask is not None:
        # mask contract: anything broadcastable to [B, H, T, S] (the layout
        # the pre-grouped formulation used); normalize then factor H→(KH, g).
        m = jnp.broadcast_to(jnp.asarray(mask), (B, H, T, S))
        m = m.reshape(B, KH, group, T, S)
        logits = jnp.where(m, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    o = jnp.einsum("bkgts,bskd->btkgd", probs, v)
    return o.reshape(B, T, H, D)


_SPARSE_LAYOUT_CACHE: Dict[tuple, Any] = {}


def _sparse_layout(cfg: TransformerConfig, seq_len: int):
    """Build (and cache) the block-sparse layout for this config + length
    (ops/sparse_attention.py sparsity configs; unidirectional = causal)."""
    key = (cfg.sparse_pattern, cfg.num_heads, cfg.sparse_block, seq_len,
           cfg.sparse_num_local_blocks, cfg.sparse_num_global_blocks,
           cfg.sparse_num_random_blocks,
           cfg.sparse_num_sliding_window_blocks)
    if key not in _SPARSE_LAYOUT_CACHE:
        from ..ops.sparse_attention import (BigBirdSparsityConfig,
                                            BSLongformerSparsityConfig,
                                            FixedSparsityConfig,
                                            VariableSparsityConfig)

        common = dict(num_heads=cfg.num_heads, block=cfg.sparse_block,
                      attention="unidirectional")
        if cfg.sparse_pattern == "fixed":
            sc = FixedSparsityConfig(
                num_local_blocks=cfg.sparse_num_local_blocks,
                num_global_blocks=cfg.sparse_num_global_blocks, **common)
        elif cfg.sparse_pattern == "bigbird":
            sc = BigBirdSparsityConfig(
                num_random_blocks=cfg.sparse_num_random_blocks,
                num_sliding_window_blocks=cfg.sparse_num_sliding_window_blocks,
                num_global_blocks=cfg.sparse_num_global_blocks, **common)
        elif cfg.sparse_pattern == "bslongformer":
            sc = BSLongformerSparsityConfig(
                num_sliding_window_blocks=cfg.sparse_num_sliding_window_blocks,
                **common)
        elif cfg.sparse_pattern == "variable":
            sc = VariableSparsityConfig(
                num_random_blocks=cfg.sparse_num_random_blocks,
                local_window_blocks=[cfg.sparse_num_local_blocks], **common)
        else:
            raise ValueError(f"unknown sparse_pattern {cfg.sparse_pattern!r}")
        _SPARSE_LAYOUT_CACHE[key] = sc.make_layout(seq_len)
    return _SPARSE_LAYOUT_CACHE[key]


def _local_attention(q, k, v, cfg: TransformerConfig, causal=True, window=0):
    if cfg.attention_impl == "sparse" and q.shape[1] == k.shape[1]:
        from ..ops.sparse_attention import sparse_attention as sparse_attn

        if cfg.attn_scale is not None:
            raise NotImplementedError(
                "attn_scale does not compose with attention_impl='sparse' "
                "(the block-sparse op bakes 1/sqrt(d))")

        if window:
            raise NotImplementedError(
                "sliding_window does not compose with attention_impl="
                "'sparse': the block-sparse layout carries no window clamp")

        # [B, T, H, D] → [B, H, T, D]; GQA (KH < H) is handled inside the
        # op via the (KH, group) factorization — K/V gathered once
        layout = _sparse_layout(cfg, q.shape[1])
        out = sparse_attn(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                          v.transpose(0, 2, 1, 3), layout, cfg.sparse_block,
                          causal=causal)
        return out.transpose(0, 2, 1, 3)
    if cfg.use_flash_attention and cfg.attention_impl != "reference" \
            and q.shape[1] == k.shape[1]:
        from ..ops.flash_attention import flash_attention, pallas_enabled

        flash = partial(flash_attention, causal=causal,
                        block_q=cfg.flash_block_q,
                        block_kv=cfg.flash_block_kv,
                        window=window, sm_scale=cfg.attn_scale)
        # kernel or XLA formulation is decided by shape and platform before
        # the call; a kernel the compiler refuses raises, it does not give
        # way to the O(T²) reference behind the caller's back
        if pallas_enabled(q, k, cfg.flash_block_q, cfg.flash_block_kv):
            return _per_device(flash, q, k, v)
        return flash(q, k, v)
    return attention_reference(q, k, v, causal=causal, window=window,
                               scale=cfg.attn_scale)


def _per_device(attn, q, k, v):
    """Run ``attn(q, k, v)`` on each device's shard of the topology mesh:
    batch over the data/fsdp axes, heads over ``tensor``. GSPMD cannot
    partition a Mosaic kernel ("wrap the call in a shard_map"), so under a
    mesh of more than one device the kernel call is made manual here —
    over whichever axes an enclosing shard_map (Ulysses, the pipeline, the
    1-bit step) has not already made manual."""
    from jax.sharding import PartitionSpec as P

    from ..compat import shard_map
    from ..parallel import topology as topo

    if not topo.has_topology():
        return attn(q, k, v)
    mesh = topo.get_topology().mesh
    manual = frozenset(jax.sharding.get_abstract_mesh().manual_axes)
    free = {a: n for a, n in mesh.shape.items() if n > 1 and a not in manual}
    if not free:
        return attn(q, k, v)
    batch = tuple(a for a in topo.BATCH_AXES if a in free)
    heads = topo.TENSOR_AXIS if topo.TENSOR_AXIS in free else None
    nb, nh = math.prod(free[a] for a in batch), free.get(heads, 1)
    if q.shape[0] % nb or q.shape[2] % nh or k.shape[2] % nh:
        raise ValueError(
            f"flash attention over mesh {dict(mesh.shape)}: q {q.shape} / "
            f"kv {k.shape} need batch divisible by {nb} ({batch}) and "
            f"heads by {nh} ({heads})")
    spec_ = P(batch or None, None, heads, None)
    # inside an enclosing shard_map the context mesh is the only legal one
    return shard_map(attn, mesh=None if manual else mesh,
                     in_specs=(spec_, spec_, spec_), out_specs=spec_,
                     axis_names=frozenset(mesh.axis_names) - manual,
                     check_vma=False)(q, k, v)


def _seq_parallel_size() -> int:
    from ..parallel import topology as topo

    if not topo.has_topology():
        return 1
    return topo.get_topology().get_sequence_parallel_world_size()


def _pipe_parallel_size() -> int:
    from ..parallel import topology as topo

    if not topo.has_topology():
        return 1
    return topo.get_topology().get_pipe_parallel_world_size()


def _attention(q, k, v, cfg: TransformerConfig, causal=True, window=0):
    """Dispatch: dense local attention, Ulysses all-to-all, or ring CP.

    Under sequence parallelism (mesh ``sequence`` axis > 1) the attention
    runs inside shard_map so the Pallas kernel operates on per-device
    shards — GSPMD cannot partition custom kernels, so the sequence comm
    (reference sequence/layer.py:37 Ulysses) is explicit here.
    """
    if cfg.position == "alibi":
        # additive logit bias: the Pallas kernel takes no bias — the XLA
        # reference fuses it (softmax shift-invariance needs only slopes·k)
        if _seq_parallel_size() > 1:
            raise NotImplementedError(
                "ALiBi models do not support sequence parallelism yet: the "
                "ring/Ulysses paths carry no logit bias; run BLOOM-family "
                "models without a sequence mesh axis")
        if cfg.attention_impl == "sparse":
            raise NotImplementedError(
                "attention_impl='sparse' does not support ALiBi models yet "
                "(the block-sparse op takes no logit bias)")
        S = k.shape[1]
        bias = alibi_slopes(cfg.num_heads)[:, None] * jnp.arange(S)[None, :]
        return attention_reference(q, k, v, causal=causal, bias=bias,
                                   scale=cfg.attn_scale)

    sp = _seq_parallel_size()
    if sp <= 1:
        return _local_attention(q, k, v, cfg, causal, window=window)
    if cfg.attention_impl == "sparse":
        raise NotImplementedError(
            "attention_impl='sparse' does not compose with the sequence "
            "mesh axis yet: the block-sparse layout is built for full "
            "sequences/heads, not the Ulysses/ring shards")

    from functools import partial as _partial

    from ..compat import shard_map
    from jax.sharding import PartitionSpec as P

    from ..parallel import topology as topo

    t = topo.get_topology()
    spec_ = P(topo.BATCH_AXES, topo.SEQUENCE_AXIS, None, None)

    if cfg.attention_impl == "ring":
        from ..sequence.ring_attention import ring_attention

        if cfg.attn_scale is not None:
            raise NotImplementedError(
                "attn_scale does not compose with ring attention yet")

        fn = shard_map(_partial(ring_attention, causal=causal,
                                axis_name=topo.SEQUENCE_AXIS,
                                window=window),
                       mesh=t.mesh, in_specs=(spec_, spec_, spec_),
                       out_specs=spec_, check_vma=False)
        return fn(q, k, v)

    # Ulysses: all-to-all heads↔sequence around dense local attention
    from ..sequence.layer import ulysses_attention

    local = _partial(_local_attention, cfg=cfg, causal=causal, window=window)

    def shard_fn(q, k, v):
        return ulysses_attention(local, q, k, v)

    fn = shard_map(shard_fn, mesh=t.mesh, in_specs=(spec_, spec_, spec_),
                   out_specs=spec_, check_vma=False)
    return fn(q, k, v)


# ------------------------------------------------------------------- the model

class CausalLM:
    """Functional causal LM. ``init(rng) -> params``; ``apply(params, tokens)
    -> logits``; ``loss(params, batch, rng) -> scalar``.

    Params layout::

        {"embed": {"wte": [V,H], ("wpe": [P,H])},
         "layers": {...stacked leaves, leading dim = num_layers...},
         "final_norm": {"w": [H], ("b": [H])},
         ("lm_head": {"w": [H,V]})}
    """

    def __init__(self, cfg: TransformerConfig):
        self.cfg = cfg
        # ZeRO++ hooks (parallel/zeropp.py, set by the training engine):
        # explicit quantized all-gather of fsdp-sharded weights. layer_
        # runs on each scan iteration's layer params, global_ on the
        # non-stacked leaves (embeddings, final norm, lm head).
        self.layer_transform = None
        self.global_transform = None
        # layer-scan compile strategy for mixed window schedules; tests
        # force "segments"/"switch" to check equivalence (_scan_layers)
        self._scan_mode = "auto"
        if cfg.attention_impl == "sparse":
            from ..utils.logging import logger

            logger.warning(
                "attention_impl='sparse' applies to training/prefill; the "
                "incremental decode path attends densely over the KV cache "
                "(same scope as the reference's training-only "
                "ops/sparse_attention)")

    # -- init ---------------------------------------------------------------
    def init(self, rng) -> Dict[str, Any]:
        cfg = self.cfg
        if cfg.is_hybrid:
            return self._init_hybrid(rng)
        h, m, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
        hd, nh, kvh, L = cfg.head_dim, cfg.num_heads, cfg.kv_heads, cfg.num_layers
        keys = jax.random.split(rng, 11)
        std = 0.02

        def normal(key, shape, scale=std):
            return (scale * jax.random.normal(key, shape)).astype(jnp.float32)

        def layer_stack(key, shape, scale=std):
            return (scale * jax.random.normal(key, (L,) + shape)).astype(jnp.float32)

        ln_w = jnp.ones((L, h), jnp.float32)
        layers = {
            "attn_norm_w": ln_w,
            "wq": layer_stack(keys[0], (h, nh * hd)),
            "wk": layer_stack(keys[1], (h, kvh * hd)),
            "wv": layer_stack(keys[2], (h, kvh * hd)),
            "wo": layer_stack(keys[3], (nh * hd, h), scale=std / math.sqrt(2 * L)),
        }
        if not cfg.shared_layernorm:
            layers["mlp_norm_w"] = ln_w
        E = cfg.moe_num_experts
        if E > 0:
            layers["router_wg"] = layer_stack(keys[10], (h, E), scale=1.0 / math.sqrt(h))
            layers["w_in"] = layer_stack(keys[4], (E, h, m))
            layers["w_out"] = layer_stack(keys[5], (E, m, h), scale=std / math.sqrt(2 * L))
            if cfg.activation == "silu":
                layers["w_gate"] = layer_stack(keys[6], (E, h, m))
        else:
            layers["w_in"] = layer_stack(keys[4], (h, m))
            layers["w_out"] = layer_stack(keys[5], (m, h), scale=std / math.sqrt(2 * L))
            if cfg.activation == "silu":
                layers["w_gate"] = layer_stack(keys[6], (h, m))
        mlp_bias = cfg.use_bias if cfg.mlp_bias is None else cfg.mlp_bias
        if cfg.norm == "layernorm":
            layers["attn_norm_b"] = jnp.zeros((L, h), jnp.float32)
            if not cfg.shared_layernorm:
                layers["mlp_norm_b"] = jnp.zeros((L, h), jnp.float32)
        if cfg.use_bias or cfg.qkv_bias:
            layers["wq_b"] = jnp.zeros((L, nh * hd), jnp.float32)
            layers["wk_b"] = jnp.zeros((L, kvh * hd), jnp.float32)
            layers["wv_b"] = jnp.zeros((L, kvh * hd), jnp.float32)
        if cfg.resolved_o_bias:
            layers["wo_b"] = jnp.zeros((L, h), jnp.float32)
        if mlp_bias:
            layers["w_in_b"] = jnp.zeros((L, m), jnp.float32)
            layers["w_out_b"] = jnp.zeros((L, h), jnp.float32)
            if cfg.activation == "silu" and E == 0:
                layers["w_gate_b"] = jnp.zeros((L, m), jnp.float32)

        params = {
            "embed": {"wte": normal(keys[7], (v, h))},
            "layers": layers,
            "final_norm": {"w": jnp.ones((h,), jnp.float32)},
        }
        if cfg.position == "learned":
            params["embed"]["wpe"] = normal(keys[8], (cfg.max_seq_len, h))
        if cfg.embedding_layernorm:
            params["embed"]["ln_w"] = jnp.ones((h,), jnp.float32)
            if cfg.norm == "layernorm":
                params["embed"]["ln_b"] = jnp.zeros((h,), jnp.float32)
        if cfg.norm == "layernorm":
            params["final_norm"]["b"] = jnp.zeros((h,), jnp.float32)
        if not cfg.tie_embeddings:
            params["lm_head"] = {"w": normal(keys[9], (h, v))}
            if cfg.lm_head_bias:
                params["lm_head"]["b"] = jnp.zeros((v,), jnp.float32)
        return params

    def _init_hybrid(self, rng) -> Dict[str, Any]:
        """``layers`` holds one tree per position of the period
        (``slot0`` …), each stacked over the periods: the scan takes one
        period an iteration."""
        from . import hybrid

        cfg = self.cfg
        h, v = cfg.hidden_size, cfg.vocab_size
        keys = jax.random.split(rng, len(cfg.layer_pattern) + 2)
        gain = jnp.zeros if cfg.norm_zero_centered else jnp.ones
        if cfg.layer_runs is not None:
            # a tree a position of each run (``run<r>_slot<i>``), stacked
            # over the run's periods; ``keys`` follow the positions as
            # ``layer_pattern`` lays them end to end
            at = iter(keys)
            layers = {f"run{r}_slot{i}": hybrid.init_slot(cfg, kind,
                                                          next(at), periods)
                      for r, (pattern, periods) in enumerate(cfg.layer_runs)
                      for i, kind in enumerate(pattern)}
        else:
            layers = {f"slot{i}": hybrid.init_slot(cfg, kind, keys[i],
                                                   cfg.num_periods,
                                                   ffn=cfg.ffn_at(i))
                      for i, kind in enumerate(cfg.layer_pattern)}
        params = {
            "embed": {"wte": (0.02 * jax.random.normal(keys[-1], (v, h))
                              ).astype(jnp.float32)},
            "layers": layers,
            "final_norm": {"w": gain((h,), jnp.float32)},
        }
        if cfg.norm == "layernorm":
            params["final_norm"]["b"] = jnp.zeros((h,), jnp.float32)
        # the lead layers' trees, each stacked over one "period"
        for j, kind in enumerate(cfg.lead_layers):
            params["layers"][f"lead{j}"] = hybrid.init_slot(
                cfg, kind, jax.random.fold_in(rng, j), 1, dense=True)
        if not cfg.tie_embeddings:
            params["lm_head"] = {"w": (0.02 * jax.random.normal(
                keys[-2], (h, v))).astype(jnp.float32)}
        return params

    # -- sharding specs -----------------------------------------------------
    def param_specs(self) -> Dict[str, Any]:
        """Logical-axis spec tree mirroring ``init``'s param tree
        (consumed by parallel/sharding.py)."""
        cfg = self.cfg
        if cfg.is_hybrid:
            from . import hybrid

            if cfg.layer_runs is not None:
                layers = {f"run{r}_slot{i}": hybrid.slot_specs(cfg, kind)
                          for r, (pattern, _) in enumerate(cfg.layer_runs)
                          for i, kind in enumerate(pattern)}
            else:
                layers = {f"slot{i}": hybrid.slot_specs(
                              cfg, kind, ffn=cfg.ffn_at(i))
                          for i, kind in enumerate(cfg.layer_pattern)}
            specs = {"embed": {"wte": spec("vocab", "embed")},
                     "layers": layers,
                     "final_norm": {"w": spec("embed")}}
            if cfg.norm == "layernorm":
                specs["final_norm"]["b"] = spec("embed")
            for j, kind in enumerate(cfg.lead_layers):
                specs["layers"][f"lead{j}"] = hybrid.slot_specs(
                    cfg, kind, dense=True)
            if not cfg.tie_embeddings:
                specs["lm_head"] = {"w": spec("embed", "vocab")}
            return specs
        layers = {
            "attn_norm_w": spec("layers", "embed"),
            "wq": spec("layers", "embed", "heads"),
            "wk": spec("layers", "embed", "kv_heads"),
            "wv": spec("layers", "embed", "kv_heads"),
            "wo": spec("layers", "heads", "embed"),
        }
        if not cfg.shared_layernorm:
            layers["mlp_norm_w"] = spec("layers", "embed")
        if cfg.moe_num_experts > 0:
            layers["router_wg"] = spec("layers", "embed", None)
            layers["w_in"] = spec("layers", "expert", "embed", "mlp")
            layers["w_out"] = spec("layers", "expert", "mlp", "embed")
            if cfg.activation == "silu":
                layers["w_gate"] = spec("layers", "expert", "embed", "mlp")
        else:
            layers["w_in"] = spec("layers", "embed", "mlp")
            layers["w_out"] = spec("layers", "mlp", "embed")
            if cfg.activation == "silu":
                layers["w_gate"] = spec("layers", "embed", "mlp")
        mlp_bias = cfg.use_bias if cfg.mlp_bias is None else cfg.mlp_bias
        if cfg.norm == "layernorm":
            layers["attn_norm_b"] = spec("layers", "embed")
            if not cfg.shared_layernorm:
                layers["mlp_norm_b"] = spec("layers", "embed")
        if cfg.use_bias or cfg.qkv_bias:
            layers["wq_b"] = spec("layers", "heads")
            layers["wk_b"] = spec("layers", "kv_heads")
            layers["wv_b"] = spec("layers", "kv_heads")
        if cfg.resolved_o_bias:
            layers["wo_b"] = spec("layers", "embed")
        if mlp_bias:
            layers["w_in_b"] = spec("layers", "mlp")
            layers["w_out_b"] = spec("layers", "embed")
            if cfg.activation == "silu" and cfg.moe_num_experts == 0:
                layers["w_gate_b"] = spec("layers", "mlp")
        specs = {
            "embed": {"wte": spec("vocab", "embed")},
            "layers": layers,
            "final_norm": {"w": spec("embed")},
        }
        if cfg.position == "learned":
            specs["embed"]["wpe"] = spec(None, "embed")
        if cfg.embedding_layernorm:
            specs["embed"]["ln_w"] = spec("embed")
            if cfg.norm == "layernorm":
                specs["embed"]["ln_b"] = spec("embed")
        if cfg.norm == "layernorm":
            specs["final_norm"]["b"] = spec("embed")
        if not cfg.tie_embeddings:
            specs["lm_head"] = {"w": spec("embed", "vocab")}
            if cfg.lm_head_bias:
                specs["lm_head"]["b"] = spec("vocab")
        return specs

    # -- one transformer block ---------------------------------------------
    def _block(self, x, lp, cos, sin, rng, deterministic: bool, window=0):
        cfg = self.cfg
        B, T, H = x.shape

        # attention (projections shared with the KV-cache/paged paths).
        # The scopes are the paged forward's (inference/v2/paged_model.py),
        # so a training trace and a serving trace read alike.
        with jax.named_scope("attn_norm"):
            h1 = _norm(x, lp["attn_norm_w"], lp.get("attn_norm_b"), cfg.norm, cfg.norm_eps)
        with jax.named_scope("qkv"):
            q, k, v = self._qkv(h1, lp, cos, sin, B, T)
        with jax.named_scope("attend"):
            attn = _attention(q, k, v, cfg, causal=True, window=window)
        with jax.named_scope("attn_out"):
            attn = _linear(attn.reshape(B, T, -1), lp["wo"], lp.get("wo_b"),
                           cfg.dtype)
            if cfg.dropout > 0 and not deterministic:
                rng, sub = jax.random.split(rng)
                attn = attn * jax.random.bernoulli(sub, 1 - cfg.dropout, attn.shape) / (1 - cfg.dropout)

        # mlp (dense or MoE; body shared with the inference paths).
        # parallel_residual (NeoX/Falcon): both branches read the SAME
        # input x; shared_layernorm (GPT-J): the mlp reads h1 itself;
        # sequential (default): mlp reads the post-attention x.
        with jax.named_scope("mlp"):    # norm, MLP and the residual adds
            if cfg.shared_layernorm:
                h2 = h1
            else:
                mlp_in = x if cfg.parallel_residual else x + attn
                h2 = _norm(mlp_in, lp["mlp_norm_w"], lp.get("mlp_norm_b"),
                           cfg.norm, cfg.norm_eps)
            y, l_aux = self._mlp_body(h2, lp, rng, deterministic)
            if cfg.dropout > 0 and not deterministic:
                rng, sub = jax.random.split(rng)
                y = y * jax.random.bernoulli(sub, 1 - cfg.dropout, y.shape) / (1 - cfg.dropout)
            return x + attn + y, l_aux

    def _mlp_body(self, h2, lp, rng, deterministic: bool):
        """Dense or MoE FFN on normed input; returns (y, aux_loss)."""
        cfg = self.cfg
        if cfg.moe_num_experts > 0:
            return self._moe_mlp(h2, lp, rng, deterministic)
        dt = cfg.dtype
        if cfg.activation == "silu":
            y = jax.nn.silu(_linear(h2, lp["w_gate"], lp.get("w_gate_b"), dt)) \
                * _linear(h2, lp["w_in"], lp.get("w_in_b"), dt)
        else:
            act = {"relu": jax.nn.relu,
                   "gelu_exact": partial(jax.nn.gelu, approximate=False),
                   }.get(cfg.activation, partial(jax.nn.gelu,
                                                 approximate=True))
            y = act(_linear(h2, lp["w_in"], lp.get("w_in_b"), dt))
        return _linear(y, lp["w_out"], lp.get("w_out_b"), dt), \
            jnp.zeros((), jnp.float32)

    def _moe_mlp(self, h2, lp, rng, deterministic):
        """GShard top-k MoE MLP (reference moe/sharded_moe.py:477): gate +
        shared dispatch/combine (moe/sharded_moe.py here) over the stacked
        expert weights, whose expert dim is sharded over the ``expert`` axis."""
        from ..moe.sharded_moe import (
            expert_mlp, moe_dispatch_combine, top1gating, top2gating)

        cfg = self.cfg
        B, T, M = h2.shape
        dt = cfg.dtype
        tokens = h2.reshape(B * T, M)
        logits = tokens.astype(jnp.float32) @ lp["router_wg"].astype(jnp.float32)
        if cfg.moe_dropless:
            from ..parallel import topology as topo

            if cfg.moe_top_k != 1:
                raise ValueError("moe_dropless supports top-1 routing")
            ep = (topo.get_topology().get_expert_parallel_world_size()
                  if topo.has_topology() else 1)
            if ep > 1:
                # expert-parallel dropless: partial-manual shard_map over
                # the expert axis (per-shard sort + ragged_dot, psum
                # combine; moe/grouped.py docstring)
                if _pipe_parallel_size() > 1:
                    raise NotImplementedError(
                        "dropless MoE + expert parallelism does not "
                        "compose with pipeline parallelism: the pipe loop "
                        "already runs inside shard_map and cannot nest "
                        "the expert-axis shard_map; use the capacity path")
                from ..moe.grouped import dropless_moe_mlp_ep

                y, l_aux = dropless_moe_mlp_ep(
                    tokens, logits, lp["w_in"], lp["w_out"],
                    lp.get("w_gate"), mesh=topo.get_topology().mesh,
                    activation=cfg.activation, dtype=dt)
                return y.reshape(B, T, M), l_aux
            from ..moe.grouped import dropless_moe_mlp

            y, l_aux = dropless_moe_mlp(
                tokens, logits, lp["w_in"], lp["w_out"], lp.get("w_gate"),
                activation=cfg.activation, dtype=dt)
            return y.reshape(B, T, M), l_aux
        gate_rng = None if deterministic else rng
        if cfg.moe_top_k == 1:
            l_aux, combine, dispatch, _ = top1gating(
                logits, cfg.moe_capacity_factor, cfg.moe_min_capacity, rng=gate_rng)
        else:
            l_aux, combine, dispatch, _ = top2gating(
                logits, cfg.moe_capacity_factor, cfg.moe_min_capacity, rng=gate_rng)

        def expert_fn(expert_in):  # [E, C, M]
            return expert_mlp(expert_in, lp["w_in"], lp["w_out"],
                              lp.get("w_gate"), cfg.activation, dt)

        y = moe_dispatch_combine(tokens.astype(dt), combine, dispatch, expert_fn)
        return y.reshape(B, T, M), l_aux

    # -- forward ------------------------------------------------------------
    def apply(self, params, tokens, rng=None, deterministic: bool = True,
              positions=None, return_aux: bool = False):
        """tokens [B, T] int32 → logits [B, T, V] (in compute dtype).
        With ``return_aux``, returns (logits, moe_aux_loss)."""
        cfg = self.cfg
        if cfg.is_hybrid:
            return self._apply_hybrid(params, tokens, positions, return_aux)
        B, T = tokens.shape
        if self.global_transform is not None:
            # gather the non-stacked weights once per step (ZeRO++ qwZ);
            # keys are dotted paths to keep leaves unambiguous
            flat = {f"{grp}.{k}": v for grp in ("embed", "final_norm", "lm_head")
                    for k, v in params.get(grp, {}).items()}
            flat = self.global_transform(flat)
            params = dict(params)
            for grp in ("embed", "final_norm", "lm_head"):
                if grp in params:
                    params[grp] = {k: flat[f"{grp}.{k}"] for k in params[grp]}
        with jax.named_scope("embed"):
            x = params["embed"]["wte"][tokens].astype(cfg.dtype)
            if cfg.embedding_layernorm:
                x = _norm(x, params["embed"]["ln_w"],
                          params["embed"].get("ln_b"), cfg.norm, cfg.norm_eps)
            if cfg.position == "rope":
                cos_full, sin_full = rope_table(cfg.max_seq_len, cfg.rot_dim,
                                                cfg.rope_theta)
                if positions is not None:
                    cos, sin = cos_full[positions], sin_full[positions]
                else:
                    cos, sin = cos_full[:T], sin_full[:T]
            else:
                if cfg.position == "learned":
                    pos = positions if positions is not None else jnp.arange(T)
                    x = x + params["embed"]["wpe"][pos].astype(cfg.dtype)
                cos = sin = jnp.zeros((T, 1), jnp.float32)
        if rng is None:
            rng = jax.random.PRNGKey(0)

        if _seq_parallel_size() > 1:
            # Ulysses/ring residency: activations live sequence-sharded
            from jax.sharding import NamedSharding, PartitionSpec
            from ..parallel import topology as topo

            t = topo.get_topology()
            x = lax.with_sharding_constraint(
                x, NamedSharding(t.mesh, PartitionSpec(
                    topo.BATCH_AXES, topo.SEQUENCE_AXIS, None)))

        block = self._block
        if cfg.remat:
            policy = None
            if cfg.remat_policy == "dots_saveable":
                policy = jax.checkpoint_policies.dots_saveable
            elif cfg.remat_policy == "nothing_saveable":
                policy = jax.checkpoint_policies.nothing_saveable
            block = jax.checkpoint(block, policy=policy,
                                   static_argnums=(5, 6))

        layer_keys = jax.random.split(rng, cfg.num_layers)
        segs = cfg.window_segments()
        pp = _pipe_parallel_size()
        if pp > 1:
            # SPMD pipeline: layer dim sharded over the pipe axis, microbatch
            # activations rotate via ppermute (parallel/pipeline.py).
            from ..parallel.pipeline import pipelined_layer_apply
            from ..parallel import topology as topo

            if len(segs) > 1:
                raise NotImplementedError(
                    "per-layer sliding windows do not compose with "
                    "pipeline parallelism: the pipe loop runs ONE compiled "
                    "block body over the layer-sharded stack; a mixed "
                    "window schedule needs one body per window run")
            win = segs[0][2]

            def layer_fn(carry, layer_slice, micro_idx):
                lp, key = layer_slice
                if self.layer_transform is not None:
                    lp = self.layer_transform(lp)
                # distinct dropout mask per microbatch
                key = jax.random.fold_in(key, micro_idx)
                return block(carry, lp, cos, sin, key, deterministic, win)

            num_micro = cfg.pipeline_microbatches or pp
            with jax.named_scope("layers"):
                x, aux_sum = pipelined_layer_apply(
                    layer_fn, (params["layers"], layer_keys), x, num_micro,
                    mesh=topo.get_topology().mesh)
            aux_losses = aux_sum[None]
        else:
            def scan_for(win):
                def scan_fn(carry, layer_params_and_key):
                    lp, key = layer_params_and_key
                    if self.layer_transform is not None:
                        lp = self.layer_transform(lp)
                    x, aux = block(carry, lp, cos, sin, key, deterministic,
                                   win)
                    return x, aux
                return scan_fn

            with jax.named_scope("layers"):
                x, aux_losses = self._scan_layers(
                    scan_for, x, (params["layers"], layer_keys))
        with jax.named_scope("final_norm"):
            x = _norm(x, params["final_norm"]["w"],
                      params["final_norm"].get("b"), cfg.norm, cfg.norm_eps)
        with jax.named_scope("logits"):
            logits = self._unembed(params, x)
        if return_aux:
            return logits, jnp.sum(aux_losses)
        return logits

    def _apply_hybrid(self, params, tokens, positions=None,
                      return_aux: bool = False):
        """The forward of a hybrid block (``cfg.layer_pattern``): one
        ``lax.scan`` over the periods, whose body runs the period's
        layers in order — compile time is O(1) in depth, whatever the
        mix. Each kind's layer is its ``reference`` (models/mixers/):
        recurrent layers start from a zero state (no cache here:
        training and the reference path)."""
        from . import hybrid
        from .mixers import KINDS, Fwd, kinds_of, own_rope_bases

        cfg = self.cfg
        B, T = tokens.shape
        scope = jax.named_scope

        def rope_at(width, theta):
            cos, sin = rope_table(cfg.max_seq_len, width, theta)
            cos, sin = ((cos[positions], sin[positions])
                        if positions is not None else (cos[:T], sin[:T]))
            return lambda t: apply_rope(t, cos, sin, cfg.rope_interleaved)

        with scope("embed"):
            x = params["embed"]["wte"][tokens].astype(cfg.dtype)
            if cfg.embed_scale != 1.0:
                x = x * jnp.asarray(cfg.embed_scale, cfg.dtype)
            ropes = {cfg.rope_theta: rope_at(cfg.rot_dim, cfg.rope_theta)}
        n_tokens = jnp.full((B,), T, jnp.int32)
        for theta, width in own_rope_bases(cfg).items():
            ropes[theta] = rope_at(width, theta)
        fwd = Fwd(shape=(B, T), n_tokens=n_tokens, ropes=ropes)

        def mixers_of(fwd):
            return {kind: KINDS[kind].reference(cfg, fwd)
                    for kind in kinds_of(cfg)}

        if cfg.layer_runs is not None:
            with scope("layers"):
                x, _ = hybrid.run_stack(cfg, x, params["layers"], fwd,
                                        mixers_of,
                                        transform=self.layer_transform)
            aux = jnp.zeros((), jnp.float32)
        else:
            mixers = mixers_of(fwd)

            def period(x, slots):
                return hybrid.run_period(cfg, x, slots, mixers,
                                         transform=self.layer_transform)

            if cfg.remat:
                period = jax.checkpoint(period)
            slots = tuple(params["layers"][f"slot{i}"]
                          for i in range(len(cfg.layer_pattern)))
            with scope("layers"):
                x, _ = hybrid.run_period(
                    cfg, x, hybrid.lead_slots(cfg, params), mixers,
                    kinds=cfg.lead_layers, dense=True,
                    transform=self.layer_transform)
                x, aux = lax.scan(period, x, slots)
        with scope("final_norm"):
            x = hybrid.final_norm(cfg, x, params["final_norm"])
        with scope("logits"):
            logits = self._unembed(params, x)
            if cfg.logit_scale != 1.0:
                logits = logits * jnp.asarray(cfg.logit_scale, logits.dtype)
        if return_aux:
            return logits, jnp.sum(aux)
        return logits

    def _no_contiguous_cache(self):
        """The v1 cache paths below keep per-token K/V for every layer;
        a hybrid block is served through inference/v2 only."""
        if self.cfg.is_hybrid:
            raise NotImplementedError(
                "a hybrid block (layer_pattern) has no contiguous-cache "
                "path: serve it through InferenceEngineV2")

    def _scan_layers(self, body_for_window, carry, xs):
        """``lax.scan`` over the stacked layer dim, split by the config's
        window schedule. ``body_for_window(w)`` returns a scan body with
        the static window ``w`` baked in — the Pallas kernels prune their
        KV grids from it. Three compile shapes:

        - uniform window → ONE scan (fast path, unchanged);
        - few contiguous runs (Qwen2's full-then-SWA, R=2) → one scan per
          run, compile cost O(R);
        - alternating schedules (GPT-Neo's global/local, R≈L) → ONE scan
          whose body ``lax.switch``-es between the D *distinct* window
          bodies on a per-layer index, compile cost O(D) instead of O(L).

        ``_scan_mode`` ("auto" | "segments" | "switch") pins a path for
        regression tests; "auto" picks switch only when it compiles fewer
        bodies than the per-segment split."""
        segs = self.cfg.window_segments()
        if len(segs) == 1:
            return lax.scan(body_for_window(segs[0][2]), carry, xs)
        distinct = sorted({w for _, _, w in segs})
        mode = self._scan_mode
        if mode == "auto":
            mode = "switch" if len(distinct) < len(segs) else "segments"
        if mode == "switch":
            windows = self.cfg.layer_windows()
            widx = jnp.asarray([distinct.index(w) for w in windows],
                               dtype=jnp.int32)
            bodies = [body_for_window(w) for w in distinct]

            def body(carry, idx_and_xs):
                idx, layer_xs = idx_and_xs
                return lax.switch(idx, bodies, carry, layer_xs)

            return lax.scan(body, carry, (widx, xs))
        ys = []
        for (start, n, win) in segs:
            seg_xs = jax.tree.map(lambda a: a[start:start + n], xs)
            carry, y = lax.scan(body_for_window(win), carry, seg_xs)
            ys.append(y)
        return carry, jax.tree.map(lambda *a: jnp.concatenate(a, axis=0),
                                   *ys)

    # -- KV-cache inference (reference inference v1: model_implementations/
    # transformers/ds_transformer.py decode path) ---------------------------
    def init_cache(self, batch_size: int, max_len: int):
        self._no_contiguous_cache()
        cfg = self.cfg
        shape = (cfg.num_layers, batch_size, max_len, cfg.kv_heads, cfg.head_dim)
        return {"k": jnp.zeros(shape, cfg.dtype), "v": jnp.zeros(shape, cfg.dtype)}

    def _prefill_impl(self, params, tokens, cache, write_kv):
        """Shared prompt-processing scaffold: embed → layer scan (each layer
        hands its K/V to ``write_kv(kc, vc, k, v) -> (kc, vc)``) → final
        norm → logits. The contiguous and paged caches differ only in the
        write."""
        self._no_contiguous_cache()
        cfg = self.cfg
        B, T = tokens.shape
        x = params["embed"]["wte"][tokens].astype(cfg.dtype)
        if cfg.embedding_layernorm:
            x = _norm(x, params["embed"]["ln_w"], params["embed"].get("ln_b"),
                      cfg.norm, cfg.norm_eps)
        cos, sin = self._pos_tables(T, None)
        if cfg.position == "learned":
            x = x + params["embed"]["wpe"][jnp.arange(T)].astype(cfg.dtype)

        def body_for(win):
            def body(carry, xs):
                x = carry
                lp, kc, vc = xs
                x, k, v = self._block_kv(x, lp, cos, sin, window=win)
                kc, vc = write_kv(kc, vc, k, v)
                return x, (kc, vc)
            return body

        x, (new_k, new_v) = self._scan_layers(
            body_for, x, (params["layers"], cache["k"], cache["v"]))
        x = _norm(x, params["final_norm"]["w"], params["final_norm"].get("b"),
                  cfg.norm, cfg.norm_eps)
        logits = self._unembed(params, x)
        return logits, {"k": new_k, "v": new_v}

    def prefill(self, params, tokens, cache):
        """Process a full prompt, filling cache[:, :, :T]. Returns
        (logits [B, T, V], cache)."""
        def write(kc, vc, k, v):
            return (lax.dynamic_update_slice(kc, k, (0, 0, 0, 0)),
                    lax.dynamic_update_slice(vc, v, (0, 0, 0, 0)))

        return self._prefill_impl(params, tokens, cache, write)

    def decode_step(self, params, cache, tokens, pos):
        """One decode step: tokens [B] at position ``pos`` (scalar int32).
        Returns (logits [B, V], cache)."""
        self._no_contiguous_cache()
        cfg = self.cfg
        B = tokens.shape[0]
        S = cache["k"].shape[2]
        x = params["embed"]["wte"][tokens][:, None, :].astype(cfg.dtype)  # [B,1,H]
        if cfg.embedding_layernorm:
            x = _norm(x, params["embed"]["ln_w"], params["embed"].get("ln_b"),
                      cfg.norm, cfg.norm_eps)
        cos, sin = self._pos_tables(1, jnp.asarray(pos)[None])
        if cfg.position == "learned":
            x = x + params["embed"]["wpe"][jnp.asarray(pos)[None]].astype(cfg.dtype)

        def body_for(win):
            def body(carry, xs):
                x = carry
                lp, kc, vc = xs
                x, kc, vc = self._block_decode(x, lp, kc, vc, cos, sin, pos,
                                               S, window=win)
                return x, (kc, vc)
            return body

        x, (new_k, new_v) = self._scan_layers(
            body_for, x, (params["layers"], cache["k"], cache["v"]))
        x = _norm(x, params["final_norm"]["w"], params["final_norm"].get("b"),
                  cfg.norm, cfg.norm_eps)
        logits = self._unembed(params, x)[:, 0]
        return logits, {"k": new_k, "v": new_v}

    # -- paged KV-cache inference (v1 decode through the paged kernel —
    # the contiguous cache is the trivial-block-table case; reference decode
    # hot loop: csrc/transformer/inference/csrc/pt_binding.cpp) -------------
    def init_paged_cache(self, batch_size: int, max_len: int,
                         block_size: int = 128):
        """Pool-layout KV cache: [L, B·NB, KH, bs, D] with sequence b owning
        the contiguous block range [b·NB, (b+1)·NB). Returns (cache, tables).
        Unlike ``init_cache``'s [B, S, ...] layout, the pool layout feeds
        ``ops/paged_attention.py`` directly — decode never materializes a
        [*, S] mask or attends past each sequence's live length."""
        self._no_contiguous_cache()
        cfg = self.cfg
        nb = -(-max_len // block_size)
        shape = (cfg.num_layers, batch_size * nb, cfg.kv_heads, block_size,
                 cfg.head_dim)
        tables = jnp.arange(batch_size * nb,
                            dtype=jnp.int32).reshape(batch_size, nb)
        return ({"k": jnp.zeros(shape, cfg.dtype),
                 "v": jnp.zeros(shape, cfg.dtype)}, tables)

    def prefill_paged(self, params, tokens, prompt_len, cache, tables):
        """Ragged prefill: ``tokens`` [B, T] right-padded, ``prompt_len``
        [B]. Causal attention over the padded batch (pad positions produce
        garbage K/V but are overwritten by decode before any query can
        attend them — the per-seq context mask in the paged kernel keeps
        them dead). Returns (logits [B, T, V], cache)."""
        self._no_contiguous_cache()
        cfg = self.cfg
        B, T = tokens.shape
        bs = cache["k"].shape[3]
        # scatter coordinates: position t of sequence b → (table[b, t//bs],
        # slot t%bs) — precomputed once, shared by every layer
        pos = jnp.arange(T)
        blk = jnp.take_along_axis(tables, (pos // bs)[None, :], axis=1)  # [B,T]
        write_blk = blk.reshape(-1)
        write_off = jnp.tile(pos % bs, B)

        def write(kc, vc, k, v):
            kc = kc.at[write_blk, :, write_off, :].set(
                k.reshape(B * T, cfg.kv_heads, cfg.head_dim))
            vc = vc.at[write_blk, :, write_off, :].set(
                v.reshape(B * T, cfg.kv_heads, cfg.head_dim))
            return kc, vc

        return self._prefill_impl(params, tokens, cache, write)

    def decode_step_paged(self, params, cache, tables, tokens, pos):
        """One ragged decode step: ``tokens`` [B] at per-sequence positions
        ``pos`` [B]. Attention runs through the Pallas paged kernel (XLA
        gather fallback off-TPU) — per-token cost scales with each
        sequence's live context, not the cache capacity. Returns
        (logits [B, V], cache)."""
        self._no_contiguous_cache()
        cfg = self.cfg
        B = tokens.shape[0]
        bs = cache["k"].shape[3]
        x = params["embed"]["wte"][tokens][:, None, :].astype(cfg.dtype)
        if cfg.embedding_layernorm:
            x = _norm(x, params["embed"]["ln_w"], params["embed"].get("ln_b"),
                      cfg.norm, cfg.norm_eps)
        pos = jnp.asarray(pos, jnp.int32)
        cos, sin = self._pos_tables(1, pos)
        if cfg.position == "rope":
            cos, sin = cos[:, None, :], sin[:, None, :]   # per-seq [B,1,R/2]
        if cfg.position == "learned":
            x = x + params["embed"]["wpe"][pos][:, None, :].astype(cfg.dtype)
        slopes = (alibi_slopes(cfg.num_heads) if cfg.position == "alibi"
                  else None)

        write_blk = jnp.take_along_axis(tables, (pos // bs)[:, None],
                                        axis=1)[:, 0]                 # [B]
        write_off = pos % bs
        n_tok = jnp.ones((B,), jnp.int32)

        def body_for(win):
            def body(carry, xs):
                x = carry
                lp, kc, vc = xs
                h1 = _norm(x, lp["attn_norm_w"], lp.get("attn_norm_b"),
                           cfg.norm, cfg.norm_eps)
                q, k, v = self._qkv(h1, lp, cos, sin, B, 1)
                kc = kc.at[write_blk, :, write_off, :].set(k[:, 0])
                vc = vc.at[write_blk, :, write_off, :].set(v[:, 0])
                from ..ops.paged_attention import paged_attention

                attn = paged_attention(q, kc, vc, tables, pos, n_tok,
                                       alibi_slopes=slopes, window=win,
                                       sm_scale=cfg.attn_scale)
                attn = _linear(attn.reshape(B, 1, -1), lp["wo"],
                               lp.get("wo_b"), cfg.dtype)
                return self._attn_mlp_merge(x, attn, lp, h1), (kc, vc)
            return body

        x, (new_k, new_v) = self._scan_layers(
            body_for, x, (params["layers"], cache["k"], cache["v"]))
        x = _norm(x, params["final_norm"]["w"], params["final_norm"].get("b"),
                  cfg.norm, cfg.norm_eps)
        logits = self._unembed(params, x)[:, 0]
        return logits, {"k": new_k, "v": new_v}

    def _pos_tables(self, T, positions):
        cfg = self.cfg
        if cfg.position != "rope":
            return jnp.zeros((T, 1), jnp.float32), jnp.zeros((T, 1), jnp.float32)
        cos_full, sin_full = rope_table(cfg.max_seq_len, cfg.rot_dim,
                                        cfg.rope_theta)
        if positions is not None:
            return cos_full[positions], sin_full[positions]
        return cos_full[:T], sin_full[:T]

    def _unembed(self, params, x):
        cfg = self.cfg
        if cfg.tie_embeddings:
            return x @ params["embed"]["wte"].T.astype(cfg.dtype)
        w = params["lm_head"]["w"]
        if isinstance(w, dict):
            # blockwise-quantized lm_head (weight serving) — same
            # dispatch as _linear
            from ..ops.quantizer import quantized_matmul

            y = quantized_matmul(x, w["qw"], w["qs"], out_dtype=cfg.dtype)
        else:
            y = x @ w.astype(cfg.dtype)
        if "b" in params.get("lm_head", {}):
            y = y + params["lm_head"]["b"].astype(cfg.dtype)
        return y

    def _qkv(self, h1, lp, cos, sin, B, T):
        cfg = self.cfg
        nh, kvh, hd = cfg.num_heads, cfg.kv_heads, cfg.head_dim
        dt = cfg.dtype
        q = _linear(h1, lp["wq"], lp.get("wq_b"), dt).reshape(B, T, nh, hd)
        k = _linear(h1, lp["wk"], lp.get("wk_b"), dt).reshape(B, T, kvh, hd)
        v = _linear(h1, lp["wv"], lp.get("wv_b"), dt).reshape(B, T, kvh, hd)
        if cfg.position == "rope":
            q = apply_rope(q, cos, sin, cfg.rope_interleaved)
            k = apply_rope(k, cos, sin, cfg.rope_interleaved)
        return q, k, v

    def _attn_mlp_merge(self, x, attn_out, lp, h1=None):
        """Shared residual wiring for the inference blocks: sequential
        (mlp reads post-attention), parallel (both branches read x), or
        shared-layernorm parallel (GPT-J: mlp reads the SAME normed h1 the
        attention read — no second norm exists)."""
        cfg = self.cfg
        if cfg.shared_layernorm:
            y, _ = self._mlp_body(h1, lp, None, True)
            return x + attn_out + y
        mlp_in = x if cfg.parallel_residual else x + attn_out
        h2 = _norm(mlp_in, lp["mlp_norm_w"], lp.get("mlp_norm_b"), cfg.norm,
                   cfg.norm_eps)
        y, _ = self._mlp_body(h2, lp, None, True)
        return x + attn_out + y

    def _block_kv(self, x, lp, cos, sin, window=0):
        """Forward block that also returns this layer's K/V (for prefill)."""
        cfg = self.cfg
        B, T, _ = x.shape
        h1 = _norm(x, lp["attn_norm_w"], lp.get("attn_norm_b"), cfg.norm, cfg.norm_eps)
        q, k, v = self._qkv(h1, lp, cos, sin, B, T)
        attn = _attention(q, k, v, cfg, causal=True, window=window)
        attn = _linear(attn.reshape(B, T, -1), lp["wo"], lp.get("wo_b"),
                       cfg.dtype)
        return self._attn_mlp_merge(x, attn, lp, h1), k, v

    def _block_decode(self, x, lp, kc, vc, cos, sin, pos, S, window=0):
        """Decode block: single token attends over the cache."""
        cfg = self.cfg
        B = x.shape[0]
        h1 = _norm(x, lp["attn_norm_w"], lp.get("attn_norm_b"), cfg.norm, cfg.norm_eps)
        q, k, v = self._qkv(h1, lp, cos, sin, B, 1)
        kc = lax.dynamic_update_slice(kc, k, (0, pos, 0, 0))
        vc = lax.dynamic_update_slice(vc, v, (0, pos, 0, 0))
        keep = jnp.arange(S) <= pos
        if window:
            keep = keep & (pos - jnp.arange(S) < window)
        mask = keep[None, None, None, :]                     # [1,1,1,S]
        bias = None
        if cfg.position == "alibi":
            bias = alibi_slopes(cfg.num_heads)[:, None] \
                * jnp.arange(S)[None, :]
        attn = attention_reference(q, kc, vc, causal=False, mask=mask,
                                   bias=bias, scale=cfg.attn_scale)
        attn = _linear(attn.reshape(B, 1, -1), lp["wo"], lp.get("wo_b"),
                       cfg.dtype)
        return self._attn_mlp_merge(x, attn, lp, h1), kc, vc

    # -- loss ---------------------------------------------------------------
    def loss(self, params, batch, rng=None):
        """batch: {"input_ids": [B,T]} (labels = shifted inputs) or
        {"input_ids", "labels"(, "loss_mask")}. Returns mean token NLL."""
        tokens = batch["input_ids"]
        labels = batch.get("labels")
        if labels is None:
            labels = tokens[:, 1:]
            tokens = tokens[:, :-1]
        mask = batch.get("loss_mask")
        logits, aux = self.apply(params, tokens, rng=rng,
                                 deterministic=rng is None, return_aux=True)
        with jax.named_scope("loss"):
            logits = logits.astype(jnp.float32)
            logz = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, labels[..., None],
                                       axis=-1)[..., 0]
            nll = logz - gold
            if mask is not None:
                loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1)
            else:
                loss = jnp.mean(nll)
            if self.cfg.moe_num_experts > 0:
                loss = loss + self.cfg.moe_aux_loss_coef * aux
            return loss

    # convenience
    def num_params(self) -> int:
        return self.cfg.num_params()
