"""``"mamba2"``: the Mamba-2 state-space layer (``ops/mamba2_ssd.py``) —
one projection to ``[z | xBC | dt]``, a depthwise causal conv with bias
over ``[x | B | C]`` and a SiLU, the recurrence over a float32 state
under ``exp(dt A)`` (``dt = softplus(dt + dt_bias)`` a head, ``A =
-exp(A_log)``, B and C shared by the heads of a group), the skip ``D x``,
an RMSNorm by group over ``y · silu(z)`` (the gate first) and the output
projection.

Its cache is the state and the conv's last inputs, not per-token K/V:
``mamba_ssm`` [L_m, slots + 1, heads, P, S] float32 and ``mamba_conv``
[L_m, slots + 1, K-1, CH] in the served type, one slot a sequence, by a
Gated DeltaNet layer's rules (``gdn.py``; the leaves are named apart from
its, so that a model may hold both): a row at ``start_pos`` 0 starts from
zero, positions at or beyond ``n_tokens`` change neither. Rows of one
token go through the step, wider ones through the chunked form at
``mamba_chunk_size``. In serving a forward of one-token rows steps the
state where it lies in its slots (``ssd.ssd_step_slots``: the leaf goes
to the kernel and comes back from it, a padded row moves none of it);
the chunked form's one row a forward is gathered and scattered.

Scopes (docs/OBSERVABILITY.md): ``mamba`` ⊃ ``mamba_proj``,
``mamba_conv``, ``mamba_scan`` (the recurrence alone: in a served
one-token forward the kernel ``mamba2_step`` and what feeds it),
``mamba_out`` and, in serving, ``mamba_state_io``: the gather of the
rows' conv tail out of the slots and the scatter back -- and, round the
chunked form only, the state's."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ...ops import gated_delta as gd
from ...ops import mamba2_ssd as ssd
from ...parallel.sharding import spec
from ..transformer import _linear
from .base import Mixer, rms

KIND = "mamba2"
scope = jax.named_scope

#: the source's ``time_step_min`` / ``max`` / ``floor``: what ``dt_bias``
#: is drawn from, and nothing else (its ``time_step_limit`` is (0, inf))
DT_INIT = (1e-3, 1e-1, 1e-4)


def dims(cfg):
    """(heads, head channels, state size, groups, inner width, conv
    channels)."""
    nh, hd, ns, g = (cfg.mamba_num_heads, cfg.mamba_head_dim,
                     cfg.mamba_state_size, cfg.mamba_n_groups)
    return nh, hd, ns, g, nh * hd, nh * hd + 2 * g * ns


def check(cfg):
    nh, hd, ns, g, inner, _ = dims(cfg)
    if min(nh, hd, ns, g) <= 0 or nh % g or inner % g \
            or cfg.mamba_conv_kernel < 2 or cfg.mamba_chunk_size <= 0:
        raise ValueError(
            "\"mamba2\" layers need mamba_num_heads, mamba_head_dim and "
            "mamba_state_size > 0, mamba_n_groups that divides the heads, "
            "a conv of two taps or more and a chunk size")


def init(cfg, w, gain):
    """Weights from the seed; ``A_log``, ``D``, ``dt_bias``, the taps and
    their bias as the source's modelling code initialises them: ``A = 1
    .. heads``, ``D = 1``, ``dt_bias`` the inverse softplus of a step
    drawn log-uniformly in ``DT_INIT``, taps and bias uniform in ±1/√K
    (a depthwise ``Conv1d``'s default)."""
    h, P = cfg.hidden_size, w.periods
    nh, hd, ns, g, inner, ch = dims(cfg)
    K = cfg.mamba_conv_kernel
    lo, hi, floor = DT_INIT
    step = jnp.maximum(jnp.exp(
        jax.random.uniform(w.key(), (P, nh), jnp.float32)
        * (math.log(hi) - math.log(lo)) + math.log(lo)), floor)
    bound = 1.0 / math.sqrt(K)
    return dict(
        mamba_w_in=w((h, inner + ch + nh)),
        mamba_conv_w=jax.random.uniform(w.key(), (P, K, ch), jnp.float32,
                                        -bound, bound),
        mamba_conv_b=jax.random.uniform(w.key(), (P, ch), jnp.float32,
                                        -bound, bound),
        mamba_A_log=jnp.broadcast_to(
            jnp.log(jnp.arange(1, nh + 1, dtype=jnp.float32)), (P, nh)),
        mamba_D=jnp.ones((P, nh), jnp.float32),
        mamba_dt_bias=step + jnp.log(-jnp.expm1(-step)),
        mamba_norm_w=jnp.ones((P, inner), jnp.float32),
        mamba_w_out=w((inner, h), w.out_std))


def specs(cfg):
    return dict(mamba_w_in=spec("layers", "embed", None),
                mamba_conv_w=spec("layers", None, None),
                mamba_conv_b=spec("layers", None),
                mamba_A_log=spec("layers", None),
                mamba_D=spec("layers", None),
                mamba_dt_bias=spec("layers", None),
                mamba_norm_w=spec("layers", None),
                mamba_w_out=spec("layers", None, "embed"))


def state(cfg, slots: int):
    """The recurrence's state (float32 whatever the served type) and the
    conv's tail."""
    nh, hd, ns, g, inner, ch = dims(cfg)
    L = cfg.layers_of(KIND)
    return dict(mamba_ssm=((L, slots, nh, hd, ns), jnp.float32),
                mamba_conv=((L, slots, cfg.mamba_conv_kernel - 1, ch),
                            cfg.dtype))


def state_bytes(cfg) -> int:
    """One layer's float32 state of one sequence."""
    nh, hd, ns = dims(cfg)[:3]
    return nh * hd * ns * 4


def mamba2_mixer(cfg, h1, lp, tail, state, n_tokens, in_slots=None):
    """The Mamba-2 layer on its normed input [B, T, H], resumed from
    ``tail`` [B, K-1, CH] and ``state`` [B, heads, P, S] (float32).
    Positions at or beyond a row's ``n_tokens`` change neither. Returns
    (y [B, T, H], new tail, new state). ``in_slots``: one-token rows
    whose state stays where the caller keeps it -- ``(x, dt, A, B, C, D)
    -> y`` steps it there, and ``state`` is None in and out."""
    B, T, _ = h1.shape
    nh, hd, ns, g, inner, ch = dims(cfg)
    dt_, f32 = cfg.dtype, jnp.float32
    with scope("mamba_proj"):
        zxd = _linear(h1, lp["mamba_w_in"], None, dt_)
        z, xbc, dt = (zxd[..., :inner], zxd[..., inner:inner + ch],
                      zxd[..., inner + ch:])
        keep = (jnp.arange(T)[None, :] < n_tokens[:, None])[..., None]
        # a masked position's step is 0: decay 1, nothing added
        dt = jnp.where(keep, jax.nn.softplus(
            dt.astype(f32) + lp["mamba_dt_bias"].astype(f32)), 0.0)
        A = -jnp.exp(lp["mamba_A_log"].astype(f32))
    with scope("mamba_conv"):
        xbc, tail = gd.causal_conv(xbc, tail, lp["mamba_conv_w"], n_tokens)
        xbc = jax.nn.silu(xbc + lp["mamba_conv_b"].astype(xbc.dtype))
        x = xbc[..., :inner].reshape(B, T, nh, hd)
        Bm = xbc[..., inner:inner + g * ns].reshape(B, T, g, ns)
        Cm = xbc[..., inner + g * ns:].reshape(B, T, g, ns)
    with scope("mamba_scan"):
        if in_slots is not None:
            y = in_slots(x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0],
                         lp["mamba_D"])[:, None]
        elif T == 1:
            y, state = ssd.ssd_step(x[:, 0], dt[:, 0], A, Bm[:, 0],
                                    Cm[:, 0], lp["mamba_D"], state)
            y = y[:, None]
        else:
            y, state = ssd.ssd_chunked(x, dt, A, Bm, Cm, lp["mamba_D"],
                                       state, chunk=cfg.mamba_chunk_size)
    with scope("mamba_out"):
        # the gate first, then the norm over each group's channels
        y = y.reshape(B, T, inner) * jax.nn.silu(z.astype(f32))
        y = rms(y.reshape(B, T, g, inner // g),
                lp["mamba_norm_w"].reshape(g, inner // g), cfg.norm_eps,
                False)
        out = _linear(y.reshape(B, T, inner).astype(dt_),
                      lp["mamba_w_out"], None, dt_)
    return out, tail, state


def reference(cfg, fwd):
    B = fwd.shape[0]
    zero = {name: jnp.zeros((B,) + shape[2:], dt)
            for name, (shape, dt) in state(cfg, B).items()}

    def mixer(h1, lp, _):
        with scope("mamba"):
            return mamba2_mixer(cfg, h1, lp, zero["mamba_conv"],
                                zero["mamba_ssm"], fwd.n_tokens)[0]
    return mixer


def paged(cfg, fwd):
    pools, slots, fresh = fwd.pools, fwd.state_slots, fwd.fresh
    stepped = fwd.shape[1] == 1

    def mixer(h1, lp, i):
        layer = fwd.layer(KIND, i)

        def in_slots(*step):
            y, pools["mamba_ssm"] = ssd.ssd_step_slots(
                pools["mamba_ssm"], layer, slots, fwd.n_tokens, fresh, *step)
            return y

        with scope("mamba"):
            with scope("mamba_state_io"):
                tail = pools["mamba_conv"][layer, slots]
                tail = jnp.where(fresh[:, None, None], 0, tail)
                state = None if stepped else jnp.where(
                    fresh[:, None, None, None], 0,
                    pools["mamba_ssm"][layer, slots])
            y, tail, state = mamba2_mixer(
                cfg, h1, lp, tail, state, fwd.n_tokens,
                in_slots if stepped else None)
            with scope("mamba_state_io"):
                pools["mamba_conv"] = pools["mamba_conv"].at[
                    layer, slots].set(tail)
                if not stepped:
                    pools["mamba_ssm"] = pools["mamba_ssm"].at[
                        layer, slots].set(state)
            return y
    return mixer


def count(cfg, staged, bucket_chunk: int, block_size: int):
    """``ssm_rows_stepped``: the one-token rows through the step;
    ``ssm_chunk_tokens``: the valid tokens through the chunked form;
    ``ssm_state_bytes``: the state bytes the forward's rows read and
    write, every Mamba-2 layer."""
    stepped = len(staged) if bucket_chunk == 1 else 0
    return {"ssm_rows_stepped": stepped,
            "ssm_chunk_tokens": 0 if stepped
            else sum(len(toks) for _, toks in staged),
            "ssm_state_bytes": 2 * len(staged) * cfg.layers_of(KIND)
            * state_bytes(cfg)}


MAMBA2 = Mixer(init=init, specs=specs, reference=reference, paged=paged,
               check=check, state=state,
               totals=("ssm_rows_stepped", "ssm_chunk_tokens",
                       "ssm_state_bytes"),
               record=("ssm_",), count=count)
