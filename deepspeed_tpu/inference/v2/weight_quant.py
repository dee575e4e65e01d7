"""int8/fp8 weight serving for the v2 ragged engine.

Counterpart of the reference's ZeRO-Inference weight-only quantization
(``deepspeed/inference/quantization/quantize.py`` /
``layers.py`` dequant-on-the-fly linear) and the FastGen fp8 path,
rebuilt on the TPU-native blockwise kernel set (``ops/quantizer.py``).
On memory-bound decode the weight stream — not FLOPs — is the wall, and
weight bytes are what cap replicas per host: quantizing the CausalLM
param tree to int8 (or float8_e4m3fn) once at engine build cuts the
resident param bytes ~3.9x vs fp32 (1 byte + 4/B scale bytes per
element) and the per-step HBM weight traffic with it (PAPERS.md: arxiv
2605.25645 low-precision serving; arxiv 2506.17615 quantize-at-the-
boundary idiom).

Representation: each quantized matmul weight ``w[..., in, out]`` becomes
a two-leaf pytree node ``{"qw": int8/fp8 [..., in, out], "qs": f32
[..., in, out/B]}`` — symmetric blockwise scales along the output dim
(``ops/quantizer.py`` format), stored alongside the payload. The node
shape is what ``models/transformer._linear`` dispatches on: a dict
weight routes through ``ops/quantizer.quantized_matmul``
(dequantize-in-kernel on the Pallas path, fused dequant-then-dot on the
XLA fallback, fp32 accumulation), an array weight takes the historical
``x @ w`` byte for byte — so ``forward``/``forward_verify``/prefill all
ride the same quantized tree with no forward-path forks.

Only the dense matmul whitelist quantizes: attention projections
(``wq``/``wk``/``wv``/``wo``), the dense MLP (``w_in``/``w_out``/
``w_gate``), and the untied ``lm_head``. Embeddings (a gather, not a
matmul), norms, biases, and MoE expert stacks (they run through the
grouped einsum path, not ``_linear``) never quantize; ``skip`` prunes
the whitelist further by name.

Under TP the scale planes shard with their weight shards: the per-leaf
block size is chosen to divide the per-shard output width (so no scale
group straddles a shard boundary — quantize-then-shard equals
shard-then-quantize), and :func:`expand_spec_tree` mirrors each
quantized leaf's logical-axis spec onto both ``qw`` and ``qs`` so
``ZeroShardingPlan`` places them together (the PR 6 KV scale-plane
treatment applied to weights; verified in the multichip dryrun).
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ...ops.quantizer import choose_block, quantize_blockwise

#: weight representations this module encodes (the config surface
#: rejects anything else up front)
WEIGHT_SUPPORTED_DTYPES = ("int8", "fp8_e4m3")

#: leaf names that may quantize — everything else in the param tree is
#: structurally not a dense matmul weight (embeddings, norms, biases)
QUANTIZABLE_LAYER_LEAVES = ("wq", "wk", "wv", "wo",
                            "w_in", "w_out", "w_gate")

#: default ``skip`` list: named subtrees/leaves excluded even though a
#: matmul could run from them — embeddings (tied unembed reads ``wte``
#: as a gather + transpose matmul and must stay exact) and the final
#: norm are listed for config self-documentation; both are *also*
#: structurally unquantizable here.
DEFAULT_SKIP = ("embed", "final_norm")


def validate_weight_quant(dtype: str, block: int) -> None:
    """Reject configurations this implementation does not encode."""
    if dtype not in WEIGHT_SUPPORTED_DTYPES:
        raise ValueError(f"weight_quant.dtype {dtype!r} not supported "
                         f"(implemented: {WEIGHT_SUPPORTED_DTYPES})")
    if int(block) < 1:
        raise ValueError(f"weight_quant.block must be >= 1, got {block}")


def is_quantized(leaf) -> bool:
    """True for the two-leaf quantized-weight node this module emits."""
    return (isinstance(leaf, dict) and set(leaf) == {"qw", "qs"})


def _eff_block(out_dim: int, want: int, tp: int) -> int:
    """Block size for one leaf: the largest divisor of the (per-shard)
    output width <= ``want``, so scale groups tile the dim and — under
    TP — never straddle a shard boundary."""
    if tp > 1 and out_dim % tp == 0:
        return choose_block(out_dim // tp, want)
    return choose_block(out_dim, want)


def quantize_weights(model_cfg, params, dtype: str = "int8",
                     block: int = 128, skip: Sequence[str] = (),
                     tp: int = 1) -> Tuple[dict, Dict[str, int]]:
    """Quantize a CausalLM param tree once (the engine-build path).

    Returns ``(new_params, stats)`` where quantized leaves are
    ``{"qw", "qs"}`` nodes and everything else is the original array
    (same objects — no copy). ``stats`` carries the byte accounting the
    serving gauges publish."""
    validate_weight_quant(dtype, block)
    skip = set(skip) | set(DEFAULT_SKIP)
    moe = getattr(model_cfg, "moe_num_experts", 0) > 0

    def quant_leaf(name: str, w):
        eff = _eff_block(int(w.shape[-1]), int(block), int(tp))
        q, s = quantize_blockwise(w, block=eff, dtype=dtype)
        return {"qw": q, "qs": s}

    out = dict(params)
    layers = dict(params["layers"])
    for name in QUANTIZABLE_LAYER_LEAVES:
        if name not in layers or name in skip:
            continue
        if moe and name in ("w_in", "w_out", "w_gate"):
            continue            # expert stacks ride the grouped path
        layers[name] = quant_leaf(name, layers[name])
    out["layers"] = layers
    if "lm_head" in params and "lm_head" not in skip:
        head = dict(params["lm_head"])
        head["w"] = quant_leaf("lm_head.w", head["w"])
        out["lm_head"] = head
    return out, param_stats(out, dtype=dtype, block=int(block))


def _leaf_bytes(leaf) -> int:
    n = 1
    for d in leaf.shape:
        n *= int(d)
    return int(jnp.dtype(leaf.dtype).itemsize) * n


def param_stats(params, dtype: str = "", block: int = 0) -> Dict[str, int]:
    """Byte accounting of a (possibly quantized) param tree:
    ``param_bytes_total`` = resident bytes of every leaf (scale planes
    included), ``param_bytes_quantized`` = bytes of the quantized nodes
    (payload + scales), ``params_quantized`` = node count. The shape the
    ``param_bytes_total``/``param_bytes_quantized`` serving gauges
    read."""
    total = quantized = nodes = 0
    for leaf in jax.tree.leaves(params, is_leaf=is_quantized):
        if is_quantized(leaf):
            b = _leaf_bytes(leaf["qw"]) + _leaf_bytes(leaf["qs"])
            quantized += b
            total += b
            nodes += 1
        else:
            total += _leaf_bytes(leaf)
    return {"param_bytes_total": total,
            "param_bytes_quantized": quantized,
            "params_quantized": nodes,
            "weight_quant_dtype": dtype,
            "weight_quant_block": block}


def expand_spec_tree(spec_tree, params):
    """Mirror a ``param_specs()`` logical-axis tree onto a quantized
    param tree: where ``params`` holds a ``{"qw", "qs"}`` node the spec
    leaf is duplicated for both members — ``qs``'s dims correspond 1:1
    to the weight's (last dim compressed by the block factor), and
    ``shard_spec_for`` already drops tensor assignments that don't
    divide, so a non-tileable scale dim degrades to replication (always
    correct: values are computed before placement)."""
    def walk(spec, par):
        if is_quantized(par):
            return {"qw": spec, "qs": spec}
        if isinstance(par, dict):
            return {k: walk(spec[k] if isinstance(spec, dict) else spec,
                            par[k])
                    for k in par}
        return spec

    if spec_tree is None:
        return None
    return walk(spec_tree, params)
