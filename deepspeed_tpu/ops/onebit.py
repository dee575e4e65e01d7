"""1-bit optimizers: error-compensated compressed-communication Adam/LAMB.

Counterpart of the reference's ``runtime/fp16/onebit/`` suite — OnebitAdam
(``runtime/fp16/onebit/adam.py``), ZeroOneAdam (``zoadam.py``), OnebitLamb
(``lamb.py``) — whose core idea is: after a full-precision warmup, the
*momentum* (not the gradient) is synchronized across data-parallel workers in
compressed form (sign + per-tensor scale) with an error-feedback buffer
carrying the quantization residual into the next step, cutting DP gradient
traffic ~32x on the reference's NCCL/MPI backends
(``runtime/comm/nccl.py:16`` compressed_allreduce).

TPU-native formulation
----------------------
The reference moves sign *bit* matrices through a two-phase
gather/scatter over NCCL. On TPU the collectives are XLA all-reduces over
ICI, and the natural compressed wire format is **int8**: each worker
quantizes its error-compensated momentum to ``sign ∈ {-1,+1}`` (int8) plus
one fp32 scale per tensor, ``lax.psum``s the int8 sign tensor (1 byte/elem
on the wire vs 4 — the scalar scales ride a second, negligible psum), and
reconstructs the average as ``(Σ signs / n) · mean(scale)``. Error feedback
is per-worker state: the optimizer's ``e`` moment carries a leading
data-parallel axis and is sharded over the ``data`` mesh axis.

These optimizers therefore run *inside* ``shard_map`` over the data axis:
the engine computes **unreduced per-worker gradients** (no GSPMD psum) and
hands them to ``warmup_step_local`` / ``compressed_step_local``, which own
all cross-worker communication — exactly the reference's contract where the
1-bit optimizer takes over gradient averaging from the engine
(``runtime/engine.py:1194`` skips the engine allreduce for these types).

Wire formats (``wire_bits``):
- **1 (default)**: true packed-bit two-phase reduction, the reference's
  ``compressed_allreduce`` (runtime/comm/nccl.py:16) re-expressed with XLA
  collectives: sign bits packed 8-per-uint8 (``jnp.packbits``), phase 1
  ``all_to_all`` scatters each worker's per-segment bit chunks + an
  all-gather of the per-worker scales, local unpack/average produces this
  worker's segment of the mean, phase 2 re-compresses the segment against
  a *server* error-feedback buffer (the reference's server_error) and
  ``all_gather``s packed bits + scales. Wire bytes ≈ 2·numel/8 per step —
  the reference's ~32x over fp32, ~8x less than the int8 format below.
- **8**: int8 sign ``psum`` — one fused all-reduce, no bit twiddling; the
  better trade on small ICI meshes where latency, not bytes, dominates.

Documented divergences from the reference (design, not omission):
- ZeroOneAdam's *local-step* intervals (skipping sync entirely for k steps)
  cannot be expressed under SPMD with replicated parameters — every worker
  must hold identical params. Its variance-freeze policy and compressed
  momentum sync are implemented; sync happens at every optimizer boundary.
- Gradient clipping / the reported ``grad_norm`` use the root-mean of
  per-worker squared norms, ``sqrt(psum(‖g_i‖²)/n)`` — an upper bound on
  the true norm of the averaged gradient (equality when workers agree).
  Computing the exact averaged-grad norm would need a full-precision psum
  of the gradients, which is exactly the traffic these optimizers remove;
  the reference has the same property (its FP16_Optimizer wrapper clips by
  the *local* norm, which also differs from the averaged-grad norm).
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from .optimizers import Optimizer, OptimizerState, _tmap, _unzip

AXIS = "data"


def _seg_len(n: int, dp: int) -> int:
    """Per-worker segment length for the two-phase wire: numel padded up so
    every worker's segment is a whole number of bytes of sign bits."""
    padded = -(-n // (dp * 8)) * dp * 8
    return padded // dp


def _sign_compress_two_phase(c, e_srv, dp: int):
    """Packed-bit two-phase compressed all-reduce (reference
    runtime/comm/nccl.py:16 semantics) over the data axis; runs inside
    shard_map.

    ``c``: this worker's error-compensated buffer (any shape);
    ``e_srv`` [seg]: this worker's *server* error-feedback segment.
    Returns ``(avg, worker_err, e_srv_new)`` where ``avg`` is the
    twice-compressed mean of the workers' contributions and ``worker_err``
    = c − sign(c)·scale is next step's worker residual.
    """
    n = c.size
    seg = _seg_len(n, dp)
    flat = jnp.pad(c.reshape(-1), (0, seg * dp - n))
    # RMS scale ‖c‖/√numel — the reference's worker_scale
    # (runtime/comm/nccl.py compressed_allreduce), not mean|c|
    scale = jnp.sqrt(jnp.mean(jnp.square(c)))
    sign_pos = flat >= 0
    packed = jnp.packbits(sign_pos)                       # [dp·seg/8] uint8
    # phase 1: worker i keeps segment i of everyone's buffer
    recv = lax.all_to_all(packed.reshape(dp, seg // 8), AXIS, 0, 0)
    scales = lax.all_gather(scale, AXIS)                  # [dp]
    signs = jnp.where(jnp.unpackbits(recv.reshape(-1)).astype(jnp.bool_),
                      1.0, -1.0).astype(c.dtype).reshape(dp, seg)
    seg_avg = jnp.mean(signs * scales[:, None], axis=0)   # [seg]
    # phase 2: re-compress the averaged segment against the server error.
    # Per-chunk server scale (each worker compresses ITS segment with its
    # own RMS scale, then the scales ride the gather — the reference's
    # per-chunk server_scale), masked to the live (non-pad) positions.
    w = lax.axis_index(AXIS)
    live = (w * seg + jnp.arange(seg)) < n                # mask pad tail
    n_live = jnp.sum(live.astype(jnp.float32))
    s = jnp.where(live, seg_avg + e_srv, 0.0)
    scale2 = jnp.sqrt(jnp.sum(jnp.square(s)) / jnp.maximum(n_live, 1.0))
    sign2_pos = s >= 0
    e_srv_new = jnp.where(live, s - jnp.where(sign2_pos, scale2, -scale2),
                          0.0).astype(e_srv.dtype)   # n_live is strong f32;
    # don't let it promote the server-error moment past its init dtype
    all_packed = lax.all_gather(jnp.packbits(sign2_pos), AXIS)  # [dp, seg/8]
    scales2 = lax.all_gather(scale2, AXIS)                # [dp]
    full_signs = jnp.where(
        jnp.unpackbits(all_packed.reshape(-1)).astype(jnp.bool_),
        1.0, -1.0).astype(c.dtype).reshape(dp, seg) * scales2[:, None]
    avg = full_signs.reshape(-1)[:n].reshape(c.shape).astype(c.dtype)
    err = c - jnp.where(sign_pos[:n].reshape(c.shape), scale, -scale)
    return avg, err, e_srv_new


def _sign_compress_psum(c, dp: int):
    """Error-feedback sign compression + int8 all-reduce over the data axis.

    A *shared* scale (pmean of the per-worker mean-abs — one scalar psum) is
    used so worker ``i``'s wire contribution is exactly ``sign(c_i)·scale``:
    the reconstructed average ``(Σ signs)·scale/n`` is then the exact mean of
    the contributions and ``err_i = c_i − sign(c_i)·scale`` is the exact
    residual — the reference's server-average semantics
    (runtime/comm/nccl.py compressed_allreduce) with O(1) extra memory
    instead of an all-gather. Returns ``(avg, err)``; runs inside shard_map.
    """
    scale = lax.pmean(jnp.mean(jnp.abs(c)), AXIS)
    sign = jnp.where(c >= 0, jnp.int8(1), jnp.int8(-1))
    # int8 sums saturate at |Σ| = dp; widen only when dp could overflow.
    wire = sign if dp <= 127 else sign.astype(jnp.int16)
    sign_sum = lax.psum(wire, AXIS)
    quantized = sign.astype(c.dtype) * scale
    avg = sign_sum.astype(c.dtype) * (scale / dp)
    return avg, c - quantized


class OneBitOptimizer(Optimizer):
    """Base for compressed-comm optimizers.

    Contract with the engine (runtime/engine.py onebit path):
    - ``dp_size`` is set by the engine before ``init`` (data-parallel world).
    - ``init(params)`` creates the ``e`` error moment with a leading
      ``dp_size`` axis (engine shards it over the ``data`` mesh axis).
    - ``warmup_step_local`` / ``compressed_step_local`` run inside
      ``shard_map``: ``grads`` are this worker's unreduced gradients and the
      ``e`` leaves arrive with a leading axis of 1 (this worker's slice).
    - The engine dispatches warmup vs compressed on ``freeze_step``
      (host-side — two compiled programs, no traced branch around
      collectives).
    """

    dp_moment_keys = frozenset({"e", "e2"})
    dp_size = 1
    freeze_step = 0
    wire_bits = 1

    def _error_init(self, params):
        return _tmap(
            lambda p: jnp.zeros((self.dp_size,) + p.shape, p.dtype), params)

    def _server_error_init(self, params):
        """Per-worker server-error segments for the packed two-phase wire
        (reference nccl.py server_error); one 1/dp-sized flat segment per
        worker per leaf. Zero-length segments under the int8 wire keep the
        moments pytree uniform at no memory cost."""
        seg = (lambda p: _seg_len(p.size, self.dp_size)) \
            if self.wire_bits == 1 else (lambda p: 0)
        return _tmap(
            lambda p: jnp.zeros((self.dp_size, seg(p)), p.dtype), params)

    def _compress(self, c, e2, dp):
        """Dispatch on the wire format. Returns (avg, worker_err, e2_new)."""
        if self.wire_bits == 1:
            return _sign_compress_two_phase(c, e2[0], dp)
        avg, err = _sign_compress_psum(c, dp)
        return avg, err, e2[0]

    def _check_wire_bits(self):
        if self.wire_bits not in (1, 8):
            raise ValueError(
                f"wire_bits must be 1 (packed two-phase) or 8 (int8 psum); "
                f"got {self.wire_bits}")

    def _frozen_c2(self) -> float:
        """Bias-correction factor of the variance at the moment it froze.
        Static Python float (freeze_step and betas are construction-time),
        so it folds into the compiled compressed-step program."""
        if not getattr(self, "bias_correction", True):
            return 1.0
        b2 = self.betas[1]
        return 1.0 - b2 ** max(int(self.freeze_step), 1)

    def step(self, params, grads, state, lr):
        raise TypeError(
            f"{type(self).__name__} communicates inside its step and must "
            "run under the engine's shard_map data-parallel path; plain "
            "step() is not supported (reference onebit optimizers likewise "
            "bypass the engine allreduce)")


class OneBitAdam(OneBitOptimizer):
    """1-bit Adam (reference ``runtime/fp16/onebit/adam.py``).

    Warmup (``step < freeze_step``): exact Adam on full-precision
    ``pmean``-averaged gradients, building up the variance estimate.
    Compression stage: the variance is frozen; each worker folds its local
    gradient into the momentum, adds its error residual, sign-compresses,
    int8-all-reduces, and applies the reconstructed averaged momentum.
    """

    name = "onebitadam"

    def __init__(self, lr=1e-3, betas=(0.9, 0.999), eps=1e-8,
                 weight_decay=0.0, freeze_step=100000, bias_correction=True,
                 wire_bits=1, **_):
        self.lr, self.betas, self.eps = lr, tuple(betas), eps
        self.weight_decay = weight_decay
        self.freeze_step = int(freeze_step)
        self.bias_correction = bias_correction
        self.wire_bits = int(wire_bits)
        self._check_wire_bits()

    def init(self, params):
        zeros = _tmap(jnp.zeros_like, params)
        return OptimizerState(
            step=jnp.zeros((), jnp.int32),
            moments={"m": zeros, "v": _tmap(jnp.zeros_like, params),
                     "e": self._error_init(params),
                     "e2": self._server_error_init(params)})

    def _corrections(self, tf):
        if not self.bias_correction:
            return 1.0, 1.0
        b1, b2 = self.betas
        return 1.0 - b1 ** tf, 1.0 - b2 ** tf

    def warmup_step_local(self, params, grads, state, lr):
        b1, b2 = self.betas
        t = state.step + 1
        c1, c2 = self._corrections(t.astype(jnp.float32))
        wd = self.weight_decay

        def upd(p, g_local, m, v, e, e2):
            g = lax.pmean(g_local, AXIS)
            if wd:  # classic Adam L2 (reference adam.py warmup path)
                g = g + wd * p
            m2 = b1 * m + (1 - b1) * g
            v2 = b2 * v + (1 - b2) * jnp.square(g)
            update = (m2 / c1) / (jnp.sqrt(v2 / c2) + self.eps)
            return p - lr * update, m2, v2, e, e2

        out = _tmap(upd, params, grads, state.moments["m"],
                    state.moments["v"], state.moments["e"],
                    state.moments["e2"])
        new_p, new_m, new_v, new_e, new_e2 = _unzip(out, 5)
        return new_p, OptimizerState(
            step=t, moments={"m": new_m, "v": new_v, "e": new_e,
                             "e2": new_e2})

    def compressed_step_local(self, params, grads, state, lr):
        b1, _ = self.betas
        t = state.step + 1
        wd = self.weight_decay
        dp = self.dp_size
        c2f = self._frozen_c2()

        def upd(p, g, m, v, e, e2):
            c = b1 * m + (1 - b1) * g + e[0]
            m2, err, e2n = self._compress(c, e2, dp)
            # v frozen at freeze_step — with its bias correction frozen
            # alongside (1-b2^freeze): v alone underestimates g² by that
            # factor forever (the bias never decays once updates stop), so
            # small freeze_steps would blow the update up ~1/(1-b2^t)×.
            # The reference omits this only because it defaults freeze_step
            # to 100k where the factor is 1.0 (docs/DIVERGENCES.md).
            update = m2 / (jnp.sqrt(v / c2f) + self.eps)
            if wd:
                update = update + wd * p
            return p - lr * update, m2, v, err[None], e2n[None]

        out = _tmap(upd, params, grads, state.moments["m"],
                    state.moments["v"], state.moments["e"],
                    state.moments["e2"])
        new_p, new_m, new_v, new_e, new_e2 = _unzip(out, 5)
        return new_p, OptimizerState(
            step=t, moments={"m": new_m, "v": new_v, "e": new_e,
                             "e2": new_e2})


class ZeroOneAdam(OneBitAdam):
    """0/1 Adam (reference ``runtime/fp16/onebit/zoadam.py``): variance
    updates are frozen after ``var_freeze_step``; momentum sync is
    1-bit-compressed past that point. Local-step sync skipping does not map
    to SPMD replicated params (see module docstring) — the accepted
    ``local_step_*`` knobs are recorded but sync runs every boundary."""

    name = "zerooneadam"

    def __init__(self, lr=1e-3, betas=(0.9, 0.999), eps=1e-8,
                 weight_decay=0.0, var_freeze_step=100000,
                 var_update_scaler=16, local_step_scaler=32678,
                 local_step_clipper=16, bias_correction=True, wire_bits=1,
                 **_):
        super().__init__(lr=lr, betas=betas, eps=eps,
                         weight_decay=weight_decay,
                         freeze_step=var_freeze_step,
                         bias_correction=bias_correction,
                         wire_bits=wire_bits)
        self.var_update_scaler = var_update_scaler
        self.local_step_scaler = local_step_scaler
        self.local_step_clipper = local_step_clipper


class OneBitLamb(OneBitOptimizer):
    """1-bit LAMB (reference ``runtime/fp16/onebit/lamb.py``): warmup runs
    exact LAMB on pmean grads while recording each tensor's trust ratio; the
    compression stage applies the frozen ratios (the reference's "scaling
    coefficients", lamb.py fused-lamb freeze) to updates built from the
    compressed averaged momentum and frozen variance."""

    name = "onebitlamb"

    def __init__(self, lr=1e-3, betas=(0.9, 0.999), eps=1e-6,
                 weight_decay=0.0, freeze_step=100000, max_coeff=10.0,
                 min_coeff=0.01, wire_bits=1, **_):
        self.lr, self.betas, self.eps = lr, tuple(betas), eps
        self.weight_decay = weight_decay
        self.freeze_step = int(freeze_step)
        self.max_coeff, self.min_coeff = max_coeff, min_coeff
        self.wire_bits = int(wire_bits)
        self._check_wire_bits()

    def init(self, params):
        return OptimizerState(
            step=jnp.zeros((), jnp.int32),
            moments={"m": _tmap(jnp.zeros_like, params),
                     "v": _tmap(jnp.zeros_like, params),
                     "ratio": _tmap(lambda p: jnp.ones((), p.dtype), params),
                     "e": self._error_init(params),
                     "e2": self._server_error_init(params)})

    def warmup_step_local(self, params, grads, state, lr):
        b1, b2 = self.betas
        t = state.step + 1
        tf = t.astype(jnp.float32)
        c1, c2 = 1.0 - b1 ** tf, 1.0 - b2 ** tf

        def upd(p, g_local, m, v, r, e, e2):
            g = lax.pmean(g_local, AXIS)
            m2 = b1 * m + (1 - b1) * g
            v2 = b2 * v + (1 - b2) * jnp.square(g)
            u = (m2 / c1) / (jnp.sqrt(v2 / c2) + self.eps) \
                + self.weight_decay * p
            p_norm = jnp.linalg.norm(p.reshape(-1))
            u_norm = jnp.linalg.norm(u.reshape(-1))
            trust = jnp.where(
                u_norm > 0, jnp.where(p_norm > 0, p_norm / u_norm, 1.0), 1.0)
            trust = jnp.clip(trust, self.min_coeff, self.max_coeff)
            return p - lr * trust * u, m2, v2, trust.astype(r.dtype), e, e2

        out = _tmap(upd, params, grads, state.moments["m"],
                    state.moments["v"], state.moments["ratio"],
                    state.moments["e"], state.moments["e2"])
        new_p, new_m, new_v, new_r, new_e, new_e2 = _unzip(out, 6)
        return new_p, OptimizerState(
            step=t, moments={"m": new_m, "v": new_v, "ratio": new_r,
                             "e": new_e, "e2": new_e2})

    def compressed_step_local(self, params, grads, state, lr):
        b1, _ = self.betas
        t = state.step + 1
        dp = self.dp_size
        c2f = self._frozen_c2()
        # the frozen ratio r was recorded against the bias-corrected update
        # (warmup_step_local): applied to an uncorrected m it would leave
        # the layer's step (1 - b1^t) short of lr·‖p‖ until the bias decays
        c1 = 1.0 - b1 ** t.astype(jnp.float32)

        def upd(p, g, m, v, r, e, e2):
            c = b1 * m + (1 - b1) * g + e[0]
            m2, err, e2n = self._compress(c, e2, dp)
            # frozen v carries its frozen bias correction (see OneBitAdam)
            u = (m2 / c1) / (jnp.sqrt(v / c2f) + self.eps) \
                + self.weight_decay * p
            return p - lr * r * u, m2, v, r, err[None], e2n[None]

        out = _tmap(upd, params, grads, state.moments["m"],
                    state.moments["v"], state.moments["ratio"],
                    state.moments["e"], state.moments["e2"])
        new_p, new_m, new_v, new_r, new_e, new_e2 = _unzip(out, 6)
        return new_p, OptimizerState(
            step=t, moments={"m": new_m, "v": new_v, "ratio": new_r,
                             "e": new_e, "e2": new_e2})


ONEBIT_OPTIMIZERS = {
    "onebitadam": OneBitAdam,
    "zerooneadam": ZeroOneAdam,
    "onebitlamb": OneBitLamb,
}
