"""What the mixer modules share: the description a kind gives of itself
(``Mixer``), what a forward hands the kind that builds its layers
(``Fwd``), the block's norm, the hold of a projection's output on its
rows, and the count of the keys a chunk's queries see."""

from __future__ import annotations

import dataclasses
from typing import (Any, Callable, Dict, List, NamedTuple, Optional, Sequence,
                    Tuple)

import jax.numpy as jnp
from jax import lax
from jax.experimental.layout import Layout, with_layout_constraint


@dataclasses.dataclass(frozen=True)
class Mixer:
    """One mixer kind, as plain functions. ``cfg`` is the model's
    ``TransformerConfig`` everywhere. A kind is a position's mixer and
    knows nothing of what follows it: the position may carry the FFN
    behind it or be the mixer alone (``cfg.layer_ffn``), and a position
    that is an FFN alone (None in ``cfg.layer_pattern``) has no kind —
    ``i`` below counts the layers of this kind, not the positions.

    - ``init(cfg, w, gain)`` -> the mixer's leaves, stacked over the
      periods: ``w(shape, scale=w.std)`` draws one at the slot's next key
      (``w.key()`` hands the key itself, ``w.periods`` and ``w.out_std``
      the stack's depth and an output projection's scale), ``gain(*shape)``
      is a norm's initial gain. ``specs(cfg)`` -> their logical axes.
    - ``reference(cfg, fwd)`` / ``paged(cfg, fwd)`` -> ``mixer(h1, lp, i)``:
      the layer on its normed input [B, T, H] with the weights ``lp``,
      the ``i``-th of its kind in the run — over one whole sequence with
      no cache (training, the reference path), and over the paged pools
      and state slots of serving, which it writes into ``fwd.pools``.
    - ``scope``: the named scope round the layer, for a kind that keeps a
      per-token cache (another opens its own inside).
    - ``check(cfg)`` raises ``ValueError`` on sizes the kind cannot run.
    - ``paged_walk``: its attention is ``ops.paged_attention
      .paged_attention`` over its group's table at the model's heads, so
      the engine can count the kernel's grid steps for it
      (``attn_steps``).
    - the cache: ``pool(cfg, block_size)`` -> its pool leaves and one
      block's shape in each (None: no per-token cache), in the layer
      group of the whole context or, ``windowed``, of the window;
      ``headless``: that cache is a row a token, no head axis;
      ``state(cfg, slots)`` -> its recurrent leaves, name -> (shape,
      dtype) (None: no state); ``rope_base(cfg)`` -> ``(width, theta)``
      where it rotates at a base of its own.
    - the counters: ``totals``, the names it adds to the engine's
      ``put_totals`` (and the fleet's registry); ``record``, the names
      and prefixes of its counts in ``last_put`` that a put of several
      forwards sums; ``count(cfg, staged, bucket_chunk, block_size)`` ->
      this forward's counts, ``staged`` its ``(sequence, tokens)`` rows
      before they are committed.
    - what it reads of other layers (a model of several runs of layers,
      ``cfg.layer_runs``): ``hands``, the values it can hand on beside
      ``x`` to the runs behind its own, and ``takes``, the ones it reads
      (``Fwd.carry``; ``cfg.run_feeds`` says which run hands what).
      ``shares``: a kind whose latest layer's pool rows this kind reads
      in serving, writing none: it has no ``pool`` of its own, its group
      and its table are that kind's, and what it ``takes`` there comes
      out of the pool. ``exits``: its serving layer can write its cache
      from every position and attend from a row's last alone
      (``Fwd.exit``).
    - ``holds``: its serving layer holds its projections' outputs to rows
      in the narrow buckets (``held``); an engine of a model with such a
      kind counts those forwards (``put_totals["forwards_held"]``)."""
    init: Callable
    specs: Callable
    reference: Callable
    paged: Callable
    scope: Optional[str] = None
    check: Callable = lambda cfg: None
    pool: Optional[Callable] = None
    windowed: bool = False
    paged_walk: bool = False
    headless: bool = False
    state: Optional[Callable] = None
    rope_base: Optional[Callable] = None
    totals: Tuple[str, ...] = ()
    record: Tuple[str, ...] = ()
    count: Optional[Callable] = None
    hands: Tuple[str, ...] = ()
    takes: Tuple[str, ...] = ()
    shares: Optional[str] = None
    exits: bool = False
    holds: bool = False


class Fwd(NamedTuple):
    """What a forward has in hand when a kind builds its layers. The
    reference forward has no cache: what it lacks is None."""
    shape: Tuple[int, int]          # rows, positions a row
    n_tokens: Any                   # [rows] valid positions
    #: rope base -> (q or k [B, T, heads, D] -> the same, rotated); the
    #: model's own base is always there
    ropes: Dict[float, Callable]
    # --- serving
    start_pos: Any = None           # [rows] positions already cached
    positions: Any = None           # [rows, C]
    block_size: int = 0
    group_of: Optional[Dict[str, int]] = None   # kind -> its layer group
    tables: Optional[List[Any]] = None          # block tables, by group
    plans: Optional[List[Any]] = None           # K/V write plans, by group
    #: the cache tree of this run of layers, written into
    pools: Optional[Dict[str, Any]] = None
    #: kind -> where the run's first layer of it sits among its group's
    #: (or the recurrent) layers
    first_layer: Optional[Dict[str, Any]] = None
    state_slots: Any = None         # [rows] slots in the state leaves
    fresh: Any = None               # [rows] bool: starts from zero
    quant: bool = False             # int8 / fp8 pools with scale leaves
    #: ``(pools, leaf, rows, plan, layer)``: rows [n, ...] into layer
    #: ``layer`` of ``pools[leaf]`` at the plan's places (kv_write.py; a
    #: quantized leaf through kv_quant.py)
    write_rows: Optional[Callable] = None
    attend: Optional[Callable] = None   # PagedCausalLM._attend
    #: the widths a learned selection's scores are taken at
    #: (``paged_model.SELECT_WIDTHS``)
    select_widths: Sequence[int] = ()
    # --- a model of several runs of layers (``cfg.layer_runs``)
    #: the values that ride beside ``x`` from run to run, by name: read by
    #: the kinds that ``takes`` them, written by those that ``hands``
    #: them, in the run ``hand`` names them for
    carry: Optional[Dict[str, Any]] = None
    hand: Sequence[str] = ()
    #: ``(kind, i)`` -> the index in the model of the run's ``i``-th layer
    #: of a kind (an int, or a traced one inside a scan)
    depth_of: Optional[Callable] = None
    #: set for the one layer in which a serving forward's rows leave but
    #: for their last valid position: this forward as the rows behind see
    #: it (one position a row, ``start_pos`` the last position's) ...
    exit: Any = None
    #: ... and on that view, [N, C, ...] -> [N, 1, ...]: the row's last
    #: valid position
    narrow: Optional[Callable] = None

    def rope(self, cfg, kind: str):
        """``kind``'s rotation at the model's base; the identity for a
        kind ``cfg.rope_kinds`` leaves out."""
        if cfg.rope_kinds is None or kind in cfg.rope_kinds:
            return self.ropes[cfg.rope_theta]
        return lambda t: t

    def layer(self, kind: str, i):
        return self.first_layer[kind] + i

    def depth(self, kind: str, i):
        """The layer's index in the model (a model of runs; None in any
        other, where no kind asks)."""
        return None if self.depth_of is None else self.depth_of(kind, i)

    def write(self, kind: str, name: str, rows, layer) -> None:
        """``rows`` [n, ...] into layer ``layer`` of ``kind``'s group's
        pool leaf ``name``, under the group's write plan."""
        self.write_rows(self.pools, self.leaf(kind, name), rows,
                        self.plans[self.group_of[kind]], layer)

    def leaf(self, kind: str, name: str) -> str:
        """A pool leaf's name in ``kind``'s layer group."""
        g = self.group_of[kind]
        return name + (str(g) if g else "")


def rms(x, w, eps, zero_centered):
    """RMSNorm over the last dim in float32; ``zero_centered``: the gain
    is ``1 + w``."""
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True) + eps)
    w32 = w.astype(jnp.float32)
    return (y * (1.0 + w32 if zero_centered else w32)).astype(dt)


def block_norm(cfg, x, w):
    return rms(x, w, cfg.norm_eps, cfg.norm_zero_centered)


def rows_major(y):
    """A projection's output [N, C, out] held to the layout the matmul
    writes, a position's row behind a position's row. Left free, a
    consumer that batches by head (a lightning layer's recurrence, a
    block-sparse layer's scores) has the compiler lay the output out by
    head through the matmul, and then it is the *weight* that is
    transposed to fit — taken out of its stack into a buffer and copied
    across, 32 MiB twice in front of every such matmul of every forward
    — where a step's rows are a few KiB (``held``)."""
    return with_layout_constraint(
        y, Layout(major_to_minor=tuple(range(y.ndim))))


def narrow(cfg, rows: int) -> bool:
    """``held``'s rule for a serving forward of ``rows`` bucket positions:
    its rows are at most a quarter of a projection's weight's, so every
    projection is held to rows. It reads the bucket alone, so the engine
    counts the forwards it holds on the host (``forwards_held``)."""
    return 4 * rows <= cfg.hidden_size


def held(cfg, always: str = ""):
    """``full_qkv``'s and ``lightning_mixer``'s ``hold`` in a serving
    forward: which of a layer's projections (``"q"``, ``"k"``, ``"v"``,
    the gate's ``"g"``) are held to rows (``rows_major``) — those in
    ``always`` at every width, each of the four while the rows it is
    taken at are narrow (``narrow``; the output's own [N, C]: an exit's
    queries are taken at a row's last position alone). Whichever side
    is laid out anew is copied, and what that costs was measured on the
    chip at 4,096 wide (PERF.md section 6, PR 46): a lightning layer's
    q, k and v at every width (0.9 ms off a 2,048-row chunk's 101, 0.4
    off a two-row step's 9.8); its gate and a block-sparse layer's four
    while the rows are few (another 0.3 ms off the step, 0.1 off a
    512-row chunk; 1 ms *onto* the 2,048-row chunk each: their rows are
    relaid in float32, behind a norm or a sigmoid, more than once); and
    at five configurations' widths (PR 61): a softmax slot's four off
    every narrow bucket, 0.36 ms of a four-row step's 3.08 at 3,072 wide
    (441 MB of weights no longer copied) down to 0.03 of a 512-row
    chunk's 14.5 at 2,048 wide — no narrow bucket lost."""
    def hold(name, y):
        if name in always or narrow(cfg, y.shape[0] * y.shape[1]):
            return rows_major(y)
        return y
    return hold


def keys_and_pairs(window: int, seen: int, n: int) -> Tuple[int, int]:
    """The keys a chunk of ``n`` tokens from position ``seen`` reads and
    the query-key pairs it multiplies, under ``window`` (0: the whole
    context): query i sees ``min(seen + i + 1, window)`` keys, and the
    chunk reads from the first key its first query sees."""
    if not window:
        return seen + n, n * seen + n * (n + 1) // 2
    short = max(0, min(n, window - seen))       # queries short of a window
    return (seen + n - max(seen - window + 1, 0),
            short * seen + short * (short + 1) // 2 + (n - short) * window)
