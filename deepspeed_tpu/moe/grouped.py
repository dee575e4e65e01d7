"""Dropless grouped-GEMM MoE (megablox-style).

Counterpart of reference ``inference/v2/kernels/cutlass_ops/moe_gemm``
(CUTLASS grouped GEMM over per-expert token groups) and the capacity-free
execution style of modern MoE serving. The GShard capacity path
(``sharded_moe.py``) pads every expert to a fixed capacity — simple to
shard, but wastes FLOPs on padding and drops overflow tokens. This path
sorts tokens by their routed expert and runs ``jax.lax.ragged_dot``
(TPU-native grouped matmul — the same op Pallas megablox kernels back)
over the true group sizes: no padding FLOPs, no dropped tokens.

Two formulations:

- ``dropless_moe_mlp`` — single-shard (no expert mesh axis): top-k over
  all experts, one sort of the (row, choice) pairs + three ``ragged_dot``
  calls over the experts this layer holds (a share ``[lo, lo + n)`` of
  them, or all).
- ``dropless_moe_mlp_ep`` — expert-parallel (round 5): a *partial-manual*
  ``shard_map`` over just the ``expert`` axis (every other mesh axis stays
  under GSPMD). Activations are replicated over the expert axis, so each
  shard already holds every token row: it sorts the tokens routed to ITS
  local experts to the front (everything else lands in a trailing dummy
  group backed by zero weights), runs the per-shard ``ragged_dot``
  grouped matmul, and a ``psum`` over the expert axis combines each
  token's single live contribution — no capacity padding, no dropped
  tokens, and the only collective is the combine. A ``ragged_all_to_all``
  dispatch over expert-sharded activations would cut per-shard compute
  from O(N) to O(N/ep) rows, but XLA:CPU cannot execute it yet, which
  would leave the path untestable on the CI mesh.

Reference counterpart: ``moe/sharded_moe.py:477`` (EP all-to-all around
expert compute) + ``inference/v2/kernels/cutlass_ops/moe_gemm/moe_gemm.cu``
(per-rank grouped GEMM). The reference cannot express the fused
gather-sort-ragged-scatter program at all — its dispatch is fixed-capacity.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax


def dropless_moe_mlp(tokens: jax.Array, router_logits: jax.Array,
                     w_in: jax.Array, w_out: jax.Array,
                     w_gate: Optional[jax.Array] = None,
                     activation: str = "gelu",
                     dtype=None, *, top_k: int = 1,
                     renormalize: bool = False,
                     held: Optional[Tuple[int, int]] = None,
                     valid: Optional[jax.Array] = None,
                     max_rows: Optional[int] = None,
                     score_func: str = "softmax",
                     select_bias: Optional[jax.Array] = None,
                     route_scale: float = 1.0,
                     period: Optional[jax.Array] = None
                     ) -> Tuple[jax.Array, jax.Array]:
    """Top-k dropless MoE FFN over the experts this layer holds.

    tokens [N, H]; router_logits [N, E] (fp32) over ALL E experts;
    w_in [n, H, M]; w_out [n, M, H]; w_gate [n, H, M] for a gated form
    (``activation`` "silu": SwiGLU, "reglu": the gate through a relu;
    without it "relu", "relu2" — the square of the relu — or a gelu),
    where
    the n experts held are ``[lo, lo + n)`` (``held = (lo, n)``; None:
    all of them). A token's ``top_k`` experts are chosen over all E and
    weighted by their scores — softmax probabilities, or with
    ``score_func`` "sigmoid" each expert's own sigmoid — (``renormalize``:
    divided by their sum; all times ``route_scale``). ``select_bias``
    [E]: the choice is the top k of score + bias, the weights stay the
    unbiased scores. Only the pairs whose expert is held are computed
    here — what the others would add is another holder's part of the sum.
    ``valid`` [N] bool: rows that are padding; they reach no expert.
    ``max_rows``: a bound the caller knows on the number of valid rows —
    the grouped GEMMs then run over that many rows and not over N.
    ``period`` (a scalar, traced in a scan): the weights are not this
    layer's own but the whole stacks of a scan's P periods,
    [P · n, ...] with period p's experts at ``[p · n, (p + 1) · n)``
    (n from ``held``, or E), and this layer is period ``period``'s. Its
    group sizes go into a vector over all P · n groups at ``period`` · n
    and the grouped matmul indexes the stack: the other periods' groups
    are empty and cost nothing, and no copy of the layer's experts is
    made in front of a kernel that cannot absorb a slice (``gmm``).

    Returns (out [N, H], aux_loss) — aux is the GShard load-balancing
    loss (E · Σ_e fraction_tokens_e · fraction_probs_e), same as
    top1gating at ``top_k`` 1.
    """
    N, H = tokens.shape
    E = router_logits.shape[-1]
    if period is None:
        n_held = w_in.shape[0]
    else:
        n_held = E if held is None else int(held[1])
        if w_in.shape[0] % n_held:
            raise ValueError(f"stacks of {w_in.shape[0]} experts are no "
                             f"whole periods of {n_held}")
    lo = 0 if held is None else int(held[0])
    if held is not None and int(held[1]) != n_held:
        raise ValueError(f"held {held} but {n_held} experts' weights")
    dtype = dtype or tokens.dtype
    if score_func not in ("softmax", "sigmoid"):
        raise ValueError(f"score_func {score_func!r}")
    sigmoid = score_func == "sigmoid"
    logits32 = router_logits.astype(jnp.float32)
    probs = jax.nn.sigmoid(logits32) if sigmoid \
        else jax.nn.softmax(logits32, axis=-1)
    if select_bias is None:
        gate, expert = lax.top_k(probs, top_k)            # [N, k]
    else:
        _, expert = lax.top_k(probs + select_bias.astype(jnp.float32), top_k)
        gate = jnp.take_along_axis(probs, expert, axis=-1)
    if renormalize:
        # sigmoid scores can all be ~0: the source's modelling code keeps
        # the quotient finite with 1e-20
        gate = gate / (jnp.sum(gate, axis=-1, keepdims=True)
                       + (1e-20 if sigmoid else 0.0))
    if route_scale != 1.0:
        gate = gate * route_scale

    # load-balance aux (reference sharded_moe.py top1gating l_aux)
    me = jnp.mean(probs / jnp.sum(probs, -1, keepdims=True) if sigmoid
                  else probs, axis=0)
    ce = jnp.mean(jnp.sum(jax.nn.one_hot(expert, E, dtype=jnp.float32),
                          axis=1), axis=0) / top_k
    l_aux = jnp.sum(me * ce) * E

    rows = jnp.arange(N)
    if max_rows is not None and max_rows < N:
        # the valid rows first (stable), cut to the bound
        rows = jnp.argsort(~valid)[:max_rows]
        tokens, gate, expert = tokens[rows], gate[rows], expert[rows]
        valid = valid[rows]
    R = tokens.shape[0]
    routed = (expert >= lo) & (expert < lo + n_held)      # [R, k]
    if valid is not None:
        routed &= valid[:, None]
    # sort the (row, choice) pairs by held expert; pairs routed nowhere
    # (padding, an absent expert) sort behind the last group, where
    # ragged_dot computes nothing
    key = jnp.where(routed, expert - lo, n_held).reshape(-1)
    order = jnp.argsort(key)                              # stable
    src = order // top_k                                  # pair -> row
    group_sizes = jnp.zeros((n_held + 1,), jnp.int32).at[key].add(1)[:-1]
    if period is not None:
        group_sizes = lax.dynamic_update_slice(
            jnp.zeros((w_in.shape[0],), jnp.int32), group_sizes,
            (period * n_held,))
    out_sorted = _ragged_expert_ffn(tokens[src].astype(dtype), group_sizes,
                                    w_in, w_out, w_gate, activation, dtype,
                                    matmul=grouped_matmul)
    # back to (row, choice) order by a gather (the inverse of the sort),
    # then the weighted sum over a row's choices; what ran behind the
    # last group (pairs routed nowhere) is dropped, whatever it holds
    inverse = jnp.zeros_like(order).at[order].set(jnp.arange(R * top_k))
    pairs = out_sorted[inverse].reshape(R, top_k, H).astype(jnp.float32)
    out = jnp.sum(jnp.where(routed[..., None], pairs * gate[..., None], 0.0),
                  axis=1)
    if R < N:
        out = jnp.zeros((N, H), jnp.float32).at[rows].set(out)
    return out.astype(dtype), l_aux


#: rows of one grid step of the grouped-matmul kernel
GMM_ROWS = 128


def grouped_matmul(lhs, rhs, group_sizes):
    """``lhs[rows of group i] @ rhs[i]`` for rows sorted by group; rows
    behind the last group are not computed (what they hold is undefined).
    lhs [m, k]; rhs [g, k, n]; group_sizes [g].

    On a TPU this is the Pallas grouped matmul that JAX ships
    (``jax.experimental.pallas.ops.tpu.megablox``; its custom call is
    named ``gmm`` and keeps the caller's scope in its ``op_name``) and
    not ``lax.ragged_dot``: XLA:TPU lowers that to custom calls of its
    own (``ragged-dot-*``) that carry no ``op_name``, so a fifth to a half
    of a step's device time would sit under no scope of the program. One
    tile of 128 rows a step and a group's whole ``[k, n]`` weight (up to
    2048 a side): a group of a few rows costs one pass over its weights,
    and groups of no rows cost nothing. Off the TPU (tests):
    ``lax.ragged_dot``."""
    from ..ops.pallas_utils import on_tpu

    if not on_tpu():
        return lax.ragged_dot(lhs, rhs, group_sizes)
    import importlib

    gmm = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm").gmm
    m, k = lhs.shape
    n = rhs.shape[-1]
    pad = -m % GMM_ROWS
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    out = gmm(lhs, rhs, group_sizes.astype(jnp.int32),
              preferred_element_type=lhs.dtype,
              tiling=(GMM_ROWS,) + gmm_tiles(k, n, rhs.dtype.itemsize))
    return out[:m] if pad else out


#: bytes of VMEM the two slots of a grid step's weight tile may take, of
#: the 16 MiB a v5e core grants a kernel (rows, accumulator and output
#: tiles beside them)
GMM_WEIGHT_TILE_BYTES = 10 * 2 ** 20


def gmm_tiles(k: int, n: int, itemsize: int = 2):
    """``(tk, tn)``: the weight tile of one grid step of the grouped
    matmul, ``[k, n]`` a group. Each side is a divisor of its dimension
    (no masked remainder), a multiple of 128 where the dimension has one
    and 2048 at most; the larger side steps down while both slots of the
    tile are over ``GMM_WEIGHT_TILE_BYTES`` (2,048 x 2,048 bf16 is 8 MiB
    a slot: refused by the chip's compiler; experts 3,072 wide run at
    1,536 x 1,536)."""
    def sides(dim):
        fit = [d for d in range(128, min(dim, 2048) + 1, 128)
               if dim % d == 0]
        return fit or [min(dim, 2048)]

    ks, ns = sides(k), sides(n)
    while 2 * ks[-1] * ns[-1] * itemsize > GMM_WEIGHT_TILE_BYTES \
            and (len(ks) > 1 or len(ns) > 1):
        longer = ks if (ks[-1] >= ns[-1] and len(ks) > 1) or len(ns) == 1 \
            else ns
        longer.pop()
    return ks[-1], ns[-1]


#: the gated forms of an expert, ``down(act(gate(x)) * up(x))`` (three
#: matrices, ``w_gate`` among them): the activation's name -> act
GATED = {"silu": jax.nn.silu, "reglu": jax.nn.relu}


def _ragged_expert_ffn(st, gs, w_in, w_out, w_gate, activation, dtype,
                       matmul=lax.ragged_dot):
    """Grouped FFN over expert-sorted tokens ``st`` with group sizes
    ``gs`` (one trailing dummy group allowed when the weights carry an
    extra zero expert). ``w_gate`` goes with the gated forms (``GATED``)
    and with them only: a gate that no form would use is refused, not
    dropped."""
    if (w_gate is not None) != (activation in GATED):
        raise ValueError(
            f"activation {activation!r} "
            + ("is gated and needs w_gate" if w_gate is None else
               f"is ungated and would drop w_gate; the gated forms are "
               f"{sorted(GATED)}"))
    h = matmul(st, w_in.astype(dtype), gs)
    if w_gate is not None:
        g = matmul(st, w_gate.astype(dtype), gs)
        h = GATED[activation](g) * h
    elif activation == "relu":
        h = jax.nn.relu(h)
    elif activation == "relu2":
        h = jnp.square(jax.nn.relu(h))
    else:
        h = jax.nn.gelu(h, approximate=activation != "gelu_exact")
    return matmul(h, w_out.astype(dtype), gs)


def dropless_moe_mlp_ep(tokens: jax.Array, router_logits: jax.Array,
                        w_in: jax.Array, w_out: jax.Array,
                        w_gate: Optional[jax.Array] = None,
                        *, mesh, axis_name: str = "expert",
                        activation: str = "gelu",
                        dtype=None) -> Tuple[jax.Array, jax.Array]:
    """Expert-parallel top-1 dropless MoE FFN (module docstring).

    tokens [N, H] and router_logits [N, E] are ordinary GSPMD arrays
    (sharded over data axes); w_in/w_out/w_gate [E, ...] carry the
    ``expert`` mesh axis on dim 0. Returns (out [N, H], aux_loss).
    """
    from ..compat import shard_map
    from jax.sharding import PartitionSpec as P

    dtype = dtype or tokens.dtype
    E = router_logits.shape[-1]
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    expert = jnp.argmax(router_logits, axis=-1)
    gate = jnp.take_along_axis(probs, expert[:, None], axis=-1)[:, 0]

    # load-balance aux from the global routing stats (same formula as the
    # single-shard path; computed under GSPMD, not inside the shard_map)
    me_frac = jnp.mean(probs, axis=0)
    ce_frac = jnp.mean(jax.nn.one_hot(expert, E, dtype=jnp.float32), axis=0)
    l_aux = jnp.sum(me_frac * ce_frac) * E

    def ep_core(tok, exp, w_in, w_out, w_gate):
        # Activations are REPLICATED over the expert axis (the engine's
        # batch sharding spans data/fsdp only), so every shard already
        # holds all N token rows — no dispatch gather needed. Each shard
        # sorts the tokens routed to ITS experts to the front, runs the
        # grouped GEMM over N rows (non-local rows land in a zero-weight
        # dummy group), and a psum over the expert axis combines each
        # token's single live contribution. Per-shard compute is O(N)
        # rows; the ideal O(N/ep) would need dynamic shapes (or a
        # ragged_all_to_all dispatch with expert-sharded activations).
        shard = lax.axis_index(axis_name)
        el = w_in.shape[0]                       # local experts E // ep
        e0 = shard * el
        local = (exp >= e0) & (exp < e0 + el)
        key = jnp.where(local, exp - e0, el)     # el = dummy group
        order = jnp.argsort(key)                 # stable: keeps token order
        st = tok[order].astype(dtype)
        gs = jnp.zeros((el + 1,), jnp.int32).at[key].add(1)
        # dummy expert el carries zero weights → exact zero output for
        # tokens owned by other shards (gelu/silu·0/relu all fix 0)
        pad = lambda w: (None if w is None else                # noqa: E731
                         jnp.concatenate([w, jnp.zeros_like(w[:1])], 0))
        o = _ragged_expert_ffn(st, gs, pad(w_in), pad(w_out), pad(w_gate),
                               activation, dtype)
        full = jnp.zeros_like(o).at[order].set(o)
        # combine: sum over expert shards (exactly one is nonzero per
        # token) — the EP combine collective; output stays replicated
        return lax.psum(full, axis_name)

    wspec = P(axis_name)
    out = shard_map(ep_core, mesh=mesh, axis_names={axis_name},
                    in_specs=(P(), P(), wspec, wspec,
                              P() if w_gate is None else wspec),
                    out_specs=P(), check_vma=False)(
        tokens, expert, w_in, w_out, w_gate)
    return out * gate[:, None].astype(dtype), l_aux
