"""``"mamba1"``: the Mamba (S6) state-space layer (``ops/selective_scan.py``)
— ``[x | z] = u W_in``; a depthwise causal conv with bias over ``x`` and a
SiLU; ``[δ | B | C] = x W_x`` (``dt_rank | S | S``); ``dt = softplus(δ W_dt
+ b_dt)`` a channel; the recurrence over a float32 state under ``exp(dt ⊗
A)``, ``A = −exp(A_log)`` a channel **and** a state index; the skip ``D
x``; the gate ``y · silu(z)`` and the output projection. No norm inside
— unless ``cfg.mamba1_inner_norm`` (Jamba's form of the layer): then ``δ``,
``B`` and ``C`` each go through an RMSNorm with a gain of its own, over
``dt_rank``, ``S`` and ``S``, between ``W_x`` and ``W_dt``
(``mamba1_dt_norm``, ``mamba1_b_norm``, ``mamba1_c_norm``; in float32,
the result in the served type, as the block's norm).

Its cache is the state and the conv's last inputs, not per-token K/V:
``mamba1_ssm`` [L_m, slots + 1, S, CH] float32 (the state index in front
of the channels: ``selective_scan.py`` says why) and ``mamba1_conv``
[L_m, slots + 1, K-1, CH] in the served type, one slot a sequence, by a
Mamba-2 layer's rules (``mamba2.py``; the leaves are named apart from
its): a row at ``start_pos`` 0 starts from zero, positions at or beyond
``n_tokens`` change neither. Rows of one token go through the step, wider
ones through the chunked form (``selective_scan.SUB`` tokens a
sub-chunk). A forward of one token a row steps the state where it lies in
its slots (``s6.s6_step_slots``: the leaf goes through it as it is, on
the chip one kernel, ``s6_step``, that reads and writes each live row's
state once and no padded row's); a chunk forward's rows (one a forward)
are gathered out of their slots and scattered back, and so is the conv's
tail either way.

In a model of several runs of layers the layer hands on its **memory**:
``y`` as the recurrence gives it, the skip added, *before* the gate — what
a gated memory unit behind reads (``gmu.py``; ``Fwd.carry["memory"]``, in
the served type).

Scopes (docs/OBSERVABILITY.md), the names a Mamba-2 layer uses: ``mamba``
⊃ ``mamba_proj`` (all three projections), ``mamba_conv``, ``mamba_scan``
(the recurrence alone: in a one-token serving forward the step over the
slots, state traffic and all), ``mamba_out``, ``mamba_norm`` (the three
inner norms, where the model has them) and, in serving,
``mamba_state_io``: the gather of the rows' conv tail (and, in a chunk
forward, state) out of the slots and the scatter back."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ...ops import gated_delta as gd
from ...ops import selective_scan as s6
from ...parallel.sharding import spec
from ..transformer import _linear
from .base import Mixer, rms

KIND = "mamba1"
scope = jax.named_scope

#: the source's ``dt_min`` / ``dt_max`` / ``dt_init_floor``: what ``b_dt``
#: is drawn from
DT_INIT = (1e-3, 1e-1, 1e-4)


def dims(cfg):
    """(inner channels, state size, dt rank, conv taps)."""
    return (cfg.mamba1_inner_size, cfg.mamba1_state_size,
            cfg.mamba1_dt_rank, cfg.mamba1_conv_kernel)


def inner_norms(cfg):
    """The inner norms' gains (``cfg.mamba1_inner_norm``; none without
    it) and what each norms: name -> width, in the order ``W_x``'s output
    is cut (δ | B | C)."""
    if not cfg.mamba1_inner_norm:
        return {}
    _, ns, rank, _ = dims(cfg)
    return {"mamba1_dt_norm": rank, "mamba1_b_norm": ns,
            "mamba1_c_norm": ns}


def check(cfg):
    ch, ns, rank, K = dims(cfg)
    if min(ch, ns, rank) <= 0 or K < 2:
        raise ValueError(
            "\"mamba1\" layers need mamba1_inner_size, mamba1_state_size "
            "and mamba1_dt_rank > 0 and a conv of two taps or more")


def init(cfg, w, gain):
    """Weights from the seed; ``A_log``, ``D``, ``b_dt``, ``W_dt``, the
    taps and their bias as the source's modelling code initialises them:
    ``A = 1 .. S`` on every channel, ``D = 1``, ``b_dt`` the inverse
    softplus of a step drawn log-uniformly in ``DT_INIT``, ``W_dt``
    uniform in ±1/√rank, taps and bias uniform in ±1/√K (a depthwise
    ``Conv1d``'s default)."""
    h, P = cfg.hidden_size, w.periods
    ch, ns, rank, K = dims(cfg)
    lo, hi, floor = DT_INIT
    step = jnp.maximum(jnp.exp(
        jax.random.uniform(w.key(), (P, ch), jnp.float32)
        * (math.log(hi) - math.log(lo)) + math.log(lo)), floor)
    bound, dt_bound = 1.0 / math.sqrt(K), 1.0 / math.sqrt(rank)
    return dict(
        mamba1_w_in=w((h, 2 * ch)),
        mamba1_conv_w=jax.random.uniform(w.key(), (P, K, ch), jnp.float32,
                                         -bound, bound),
        mamba1_conv_b=jax.random.uniform(w.key(), (P, ch), jnp.float32,
                                         -bound, bound),
        mamba1_w_x=w((ch, rank + 2 * ns)),
        mamba1_w_dt=jax.random.uniform(w.key(), (P, rank, ch), jnp.float32,
                                       -dt_bound, dt_bound),
        mamba1_dt_b=step + jnp.log(-jnp.expm1(-step)),
        mamba1_A_log=jnp.broadcast_to(
            jnp.log(jnp.arange(1, ns + 1, dtype=jnp.float32))[:, None],
            (P, ns, ch)),
        mamba1_D=jnp.ones((P, ch), jnp.float32),
        mamba1_w_out=w((ch, h), w.out_std),
        **{name: gain(width) for name, width in inner_norms(cfg).items()})


def specs(cfg):
    return dict(mamba1_w_in=spec("layers", "embed", None),
                mamba1_conv_w=spec("layers", None, None),
                mamba1_conv_b=spec("layers", None),
                mamba1_w_x=spec("layers", None, None),
                mamba1_w_dt=spec("layers", None, None),
                mamba1_dt_b=spec("layers", None),
                mamba1_A_log=spec("layers", None, None),
                mamba1_D=spec("layers", None),
                mamba1_w_out=spec("layers", None, "embed"),
                **{name: spec("layers", None) for name in inner_norms(cfg)})


def state(cfg, slots: int):
    """The recurrence's state (float32 whatever the served type) and the
    conv's tail."""
    ch, ns, _, K = dims(cfg)
    L = cfg.layers_of(KIND)
    return dict(mamba1_ssm=((L, slots, ns, ch), jnp.float32),
                mamba1_conv=((L, slots, K - 1, ch), cfg.dtype))


def state_bytes(cfg) -> int:
    """One layer's float32 state of one sequence."""
    ch, ns = dims(cfg)[:2]
    return ch * ns * 4


def mamba1_mixer(cfg, h1, lp, tail, state, n_tokens, in_slots=None):
    """The S6 layer on its normed input [B, T, H], resumed from ``tail``
    [B, K-1, CH] and ``state`` [B, S, CH] (float32). Positions at or
    beyond a row's ``n_tokens`` change neither. Returns (out [B, T, H],
    new tail, new state, the memory: y [B, T, CH] before the gate).
    ``in_slots``: one-token rows whose state stays where the caller keeps
    it -- ``(x, dt, A, B, C, D) -> y`` steps it there, and ``state`` is
    None in and out."""
    B, T, _ = h1.shape
    ch, ns, rank, _ = dims(cfg)
    dt_, f32 = cfg.dtype, jnp.float32
    with scope("mamba_proj"):
        xz = _linear(h1, lp["mamba1_w_in"], None, dt_)
        x, z = xz[..., :ch], xz[..., ch:]
    with scope("mamba_conv"):
        x, tail = gd.causal_conv(x, tail, lp["mamba1_conv_w"], n_tokens)
        x = jax.nn.silu(x + lp["mamba1_conv_b"].astype(x.dtype))
    with scope("mamba_proj"):
        dbc = _linear(x, lp["mamba1_w_x"], None, dt_)
        Bm, Cm = dbc[..., rank:rank + ns], dbc[..., rank + ns:]
    delta = None
    if cfg.mamba1_inner_norm:
        with scope("mamba_norm"):
            delta, Bm, Cm = (
                rms(a, lp[name], cfg.norm_eps, False)
                for a, name in zip((dbc[..., :rank], Bm, Cm),
                                   inner_norms(cfg)))
    with scope("mamba_proj"):
        keep = (jnp.arange(T)[None, :] < n_tokens[:, None])[..., None]
        # a masked position's step is 0: decay 1, nothing added
        dt = jnp.where(keep, jax.nn.softplus(
            _linear(dbc[..., :rank] if delta is None else delta,
                    lp["mamba1_w_dt"], None, dt_
                    ).astype(f32) + lp["mamba1_dt_b"].astype(f32)), 0.0)
        A = -jnp.exp(lp["mamba1_A_log"].astype(f32))
    with scope("mamba_scan"):
        if in_slots is not None:
            y = in_slots(x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0],
                         lp["mamba1_D"])[:, None]
        elif T == 1:
            y, state = s6.s6_step(x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0],
                                  lp["mamba1_D"], state)
            y = y[:, None]
        else:
            y, state = s6.s6_chunked(x, dt, A, Bm, Cm, lp["mamba1_D"],
                                     state)
    with scope("mamba_out"):
        memory = y.astype(dt_)
        out = _linear((y * jax.nn.silu(z.astype(f32))).astype(dt_),
                      lp["mamba1_w_out"], None, dt_)
    return out, tail, state, memory


def reference(cfg, fwd):
    B = fwd.shape[0]
    zero = {name: jnp.zeros((B,) + shape[2:], dt)
            for name, (shape, dt) in state(cfg, B).items()}

    def mixer(h1, lp, _):
        with scope("mamba"):
            out, _, _, memory = mamba1_mixer(
                cfg, h1, lp, zero["mamba1_conv"], zero["mamba1_ssm"],
                fwd.n_tokens)
            if "memory" in fwd.hand:
                fwd.carry["memory"] = memory
            return out
    return mixer


def paged(cfg, fwd):
    pools, slots, fresh = fwd.pools, fwd.state_slots, fwd.fresh
    stepped = fwd.shape[1] == 1

    def mixer(h1, lp, i):
        layer = fwd.layer(KIND, i)

        def in_slots(*step):
            y, pools["mamba1_ssm"] = s6.s6_step_slots(
                pools["mamba1_ssm"], layer, slots, fwd.n_tokens, fresh,
                *step)
            return y

        with scope("mamba"):
            with scope("mamba_state_io"):
                tail = jnp.where(fresh[:, None, None], 0,
                                 pools["mamba1_conv"][layer, slots])
                state = None if stepped else jnp.where(
                    fresh[:, None, None], 0,
                    pools["mamba1_ssm"][layer, slots])
            out, tail, state, memory = mamba1_mixer(
                cfg, h1, lp, tail, state, fwd.n_tokens,
                in_slots if stepped else None)
            with scope("mamba_state_io"):
                pools["mamba1_conv"] = pools["mamba1_conv"].at[
                    layer, slots].set(tail)
                if not stepped:
                    pools["mamba1_ssm"] = pools["mamba1_ssm"].at[
                        layer, slots].set(state)
            if "memory" in fwd.hand:
                fwd.carry["memory"] = memory
            return out
    return mixer


def count(cfg, staged, bucket_chunk: int, block_size: int):
    """``ssm_rows_stepped``: the one-token rows through the step;
    ``ssm_chunk_tokens``: the valid tokens through the chunked form;
    ``ssm_state_bytes``: the state bytes the forward's rows read and
    write, every S6 layer (the names a Mamba-2 layer counts under)."""
    stepped = len(staged) if bucket_chunk == 1 else 0
    return {"ssm_rows_stepped": stepped,
            "ssm_chunk_tokens": 0 if stepped
            else sum(len(toks) for _, toks in staged),
            "ssm_state_bytes": 2 * len(staged) * cfg.layers_of(KIND)
            * state_bytes(cfg)}


MAMBA1 = Mixer(init=init, specs=specs, reference=reference, paged=paged,
               check=check, state=state,
               totals=("ssm_rows_stepped", "ssm_chunk_tokens",
                       "ssm_state_bytes"),
               record=("ssm_",), count=count, hands=("memory",))
