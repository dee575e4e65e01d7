"""v2 module registry + implementation heuristics.

Counterpart of the reference's module registry / heuristics layer
(``inference/v2/modules/module_registry.py`` ``DSModuleRegistry`` +
``heuristics.py:179`` ``instantiate_attention`` et al.): every serving op
is a *module type* with one or more named implementations; a heuristic
picks the best implementation for the current config/hardware, and callers
may force one by name. The reference had exactly one implementation per
type ("currently a stub"); here each type registers the genuinely distinct
implementations the framework already ships:

- ``attention``: the Pallas block-table kernel (``ops/paged_attention``)
  vs the XLA gather formulation (off-TPU fallback / numeric reference).
- ``flash_attention``: the Pallas training kernel vs the grouped-einsum
  XLA reference (``ops/flash_attention``).
- ``moe``: dropless ``lax.ragged_dot`` grouped GEMM (``moe/grouped``) vs
  the capacity-factor einsum path (``moe/sharded_moe``).
- ``linear``: plain dense matmul vs weight-only-quantized int8/int4
  (``inference/quantization``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional

from ...ops.pallas_utils import on_tpu


@dataclasses.dataclass(frozen=True)
class ImplEntry:
    """One named implementation of a module type."""
    name: str
    factory: Callable[..., Callable]      # (**ctx) -> forward callable
    supports: Callable[..., bool]         # (**ctx) -> can run this config?
    priority: int = 0                     # higher wins among supported


class DSModuleRegistry:
    """Registry of module-type → named implementations (reference
    module_registry.py ``DSModuleRegistryBase`` collapsed into one table —
    the per-type ABC hierarchy is torch-module machinery jax doesn't
    need)."""

    _registry: Dict[str, Dict[str, ImplEntry]] = {}

    @classmethod
    def register(cls, module_type: str, name: str,
                 factory: Callable[..., Callable],
                 supports: Optional[Callable[..., bool]] = None,
                 priority: int = 0) -> None:
        entry = ImplEntry(name, factory, supports or (lambda **ctx: True),
                          priority)
        cls._registry.setdefault(module_type, {})[name] = entry

    @classmethod
    def implementations(cls, module_type: str) -> List[str]:
        return sorted(cls._registry.get(module_type, {}))

    @classmethod
    def instantiate(cls, module_type: str, name: Optional[str] = None,
                    **ctx) -> Callable:
        """Named lookup, or the highest-priority implementation whose
        ``supports(**ctx)`` accepts the context."""
        impls = cls._registry.get(module_type)
        if not impls:
            raise KeyError(f"no implementations registered for "
                           f"{module_type!r}")
        if name is not None:
            if name not in impls:
                raise KeyError(
                    f"{module_type!r} has no implementation {name!r}; "
                    f"known: {sorted(impls)}")
            return impls[name].factory(**ctx)
        viable = [e for e in impls.values() if e.supports(**ctx)]
        if not viable:
            raise RuntimeError(
                f"no {module_type!r} implementation supports the config "
                f"{ctx}; known: {sorted(impls)}")
        best = max(viable, key=lambda e: e.priority)
        return best.factory(**ctx)


# ------------------------------------------------------------ registrations

def _attn_pallas_supports(num_heads=0, kv_heads=0, head_dim=0,
                          force_interpret=False, **_):
    from ...ops.paged_attention import pallas_supported

    return pallas_supported(num_heads, kv_heads, head_dim, force_interpret)


def _attn_pallas_factory(force_interpret=False, **_):
    from ...ops import paged_attention as pa

    if force_interpret and not on_tpu():
        # selection must mean execution: run the kernel in interpreter
        # mode off-TPU instead of letting the runtime dispatch silently
        # fall back to the XLA gather
        def fn(q, kc, vc, tables, start_pos, n_tokens, alibi_slopes=None,
               window=0, sm_scale=None, k_scale=None, v_scale=None,
               layer=None):
            return pa._paged_pallas(q, kc, vc, tables, start_pos, n_tokens,
                                    alibi_slopes=alibi_slopes,
                                    window=window, sm_scale=sm_scale,
                                    k_scale=k_scale, v_scale=v_scale,
                                    layer=layer, interpret=True)

        fn.__name__ = "paged_attention_interpret"
        return fn
    return pa.paged_attention


def _attn_xla_factory(**_):
    from ...ops.paged_attention import paged_attention_xla

    return paged_attention_xla


DSModuleRegistry.register("attention", "pallas_paged", _attn_pallas_factory,
                          supports=_attn_pallas_supports, priority=10)
DSModuleRegistry.register("attention", "xla_gather", _attn_xla_factory)


def _flash_pallas_supports(seq_len=0, head_dim=0, block_q=512, block_kv=512,
                           force_interpret=False, **_):
    from ...ops import flash_attention as fa

    return (fa._pallas_ok(seq_len, seq_len, head_dim, block_q, block_kv)
            and (on_tpu() or force_interpret or fa._FORCE_INTERPRET))


def _flash_pallas_factory(**_):
    from ...ops.flash_attention import flash_attention

    return flash_attention


def _flash_xla_factory(**_):
    from ...ops.flash_attention import _attention_xla

    return _attention_xla


DSModuleRegistry.register("flash_attention", "pallas_flash",
                          _flash_pallas_factory,
                          supports=_flash_pallas_supports, priority=10)
DSModuleRegistry.register("flash_attention", "xla_reference",
                          _flash_xla_factory)


def _moe_dropless_supports(moe_dropless=False, expert_parallel=1, **_):
    # r5: EP composes via the partial-manual expert-axis shard_map
    # (moe/grouped.py dropless_moe_mlp_ep)
    return bool(moe_dropless)


def _moe_dropless_factory(expert_parallel=1, mesh=None, **_):
    if expert_parallel > 1:
        from functools import partial

        from ...moe.grouped import dropless_moe_mlp_ep
        from ...parallel import topology as topo

        if mesh is None:
            mesh = topo.get_topology().mesh
        got = int(dict(zip(mesh.axis_names, mesh.devices.shape)
                       ).get("expert", 1))
        if got != expert_parallel:
            raise ValueError(
                f"expert_parallel={expert_parallel} but the mesh's expert "
                f"axis is {got} — set the topology (or pass mesh=) before "
                "instantiating the EP dropless MoE")
        return partial(dropless_moe_mlp_ep, mesh=mesh)
    from ...moe.grouped import dropless_moe_mlp

    return dropless_moe_mlp


def _moe_capacity_factory(**_):
    from ...moe.sharded_moe import moe_dispatch_combine

    return moe_dispatch_combine


DSModuleRegistry.register("moe", "dropless_ragged", _moe_dropless_factory,
                          supports=_moe_dropless_supports, priority=10)
DSModuleRegistry.register("moe", "capacity_einsum", _moe_capacity_factory)


def _linear_quant_supports(quant_bits=0, **_):
    return quant_bits in (4, 8)


def _linear_quant_factory(quant_bits=8, **_):
    from ..quantization import QuantTensor, quantize_array

    def prepare(w):
        """Quantize a weight once (int8/int4 resident in HBM); pass the
        result as ``w`` so the forward never re-quantizes."""
        return quantize_array(w, bits=quant_bits)

    def fn(x, w, b=None):
        # dequant fuses into the consumer matmul under jit
        if not isinstance(w, QuantTensor):
            w = prepare(w)
        y = x @ w.dequantize()
        return y if b is None else y + b

    fn.prepare = prepare
    return fn


def _linear_dense_factory(**_):
    def fn(x, w, b=None):
        y = x @ w
        return y if b is None else y + b

    return fn


DSModuleRegistry.register("linear", "weight_only_quant",
                          _linear_quant_factory,
                          supports=_linear_quant_supports, priority=10)
DSModuleRegistry.register("linear", "dense", _linear_dense_factory)


# --------------------------------------------------------------- heuristics

def instantiate_attn(model_cfg, name: Optional[str] = None,
                     force_interpret: bool = False) -> Callable:
    """Pick the serving attention implementation (reference
    heuristics.py:179 ``instantiate_attention``). Default policy: the
    Pallas block-table kernel whenever the hardware/shape contract holds,
    else the XLA gather."""
    return DSModuleRegistry.instantiate(
        "attention", name,
        num_heads=model_cfg.num_heads, kv_heads=model_cfg.kv_heads,
        head_dim=model_cfg.head_dim, force_interpret=force_interpret)


def instantiate_flash_attn(model_cfg, seq_len: int,
                           name: Optional[str] = None,
                           force_interpret: bool = False) -> Callable:
    return DSModuleRegistry.instantiate(
        "flash_attention", name,
        seq_len=seq_len, head_dim=model_cfg.head_dim,
        block_q=model_cfg.flash_block_q, block_kv=model_cfg.flash_block_kv,
        force_interpret=force_interpret)


def instantiate_moe(model_cfg, expert_parallel: int = 1,
                    name: Optional[str] = None) -> Callable:
    return DSModuleRegistry.instantiate(
        "moe", name, moe_dropless=model_cfg.moe_dropless,
        expert_parallel=expert_parallel)


def instantiate_linear(quant_bits: int = 0,
                       name: Optional[str] = None) -> Callable:
    return DSModuleRegistry.instantiate("linear", name,
                                        quant_bits=quant_bits)
