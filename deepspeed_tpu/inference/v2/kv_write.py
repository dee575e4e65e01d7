"""The paged KV write: a read-modify-write of only the touched blocks.

The engine's cache is one stacked pool per leaf, ``[L, NB, KH, bs, D]``,
that the paged forward carries through its layer scan and updates in
place (``paged_model.py``; docs/SERVING.md "The pool contract"). A ragged
chunk's K/V lands in at most ``TB = (C + 2·bs - 2)//bs`` pool blocks per
sequence (C tokens from the last slot of a block; one block for a decode
token), a *static* bound — so a layer's write is:

1. gather the touched blocks ``pool[layer, ids]`` — ``[N, TB, KH, bs, D]``;
2. place the new rows at their (block, slot) inside that small view — a
   select between the old content and the row each slot receives, no
   scatter;
3. scatter the whole blocks back, ``pool.at[layer, ids].set(...)``,
   drop-mode for the ``NB`` sentinel of rows that write nothing.

Why whole blocks and not the rows themselves: the pool's layout is the
Pallas kernel's (the trailing ``[bs, D]`` is one VMEM tile, slots on the
sublanes). A scatter whose index dimensions ``(layer, block)`` lead and
whose window ``[KH, bs, D]`` trails is that layout as it stands, and XLA
runs it in place on the carried buffer. The per-token scatter
``pool.at[layer, blk, :, slot, :]`` has ``KH`` *between* its index
dimensions: XLA transposes the operand to ``[L·NB·bs, KH, D]`` to run it
and back for the kernel — two copies of the whole pool per forward
(described-v5e compile, Pythia-1.4B: 3.94 GiB of temporaries at
``[16, 1]``). The traffic of the block form is the touched blocks alone:
16 decoding sequences × 262 KB, read and written. Step 2 had the same
fault in small — placing the rows by scatter made XLA transpose the
gathered view there and back — hence the select.

A latent pool (``[L, NB, bs, W]``: a token's row has no head axis,
docs/SERVING.md "The pool contract") runs the same three steps on blocks
``[bs, W]``: its index dimensions already lead.

int8/fp8 pools run the same three steps with a dequantize before step 2
and a re-quantize after it (``kv_quant.quantized_block_write``).

A merged forward (``paged_model._parts``) writes twice a layer, a plan
each: its chunk row's blocks, then its one-token rows'. The two plans
share no block -- a sequence is a row of one part, a padded row of the
other -- so the writes are two in-place scatters on the one carried
buffer, in either order.
"""

from __future__ import annotations

from typing import Dict

import jax.numpy as jnp


def touched_block_plan(block_tables, start_pos, n_tokens, chunk: int,
                       block_size: int, num_blocks: int) -> Dict[str, object]:
    """Static-shape plan of the pool blocks this step's KV writes touch.

    A row writing ``n_tokens`` new tokens from ``start_pos`` lands in the
    logical blocks ``start_pos//bs .. (start_pos+n_tokens-1)//bs`` — at
    most ``TB = (C + 2·bs - 2)//bs`` of them for a chunk width C, whatever
    the alignment (reached when the chunk starts in a block's last slot).
    The plan is layer-invariant (same coordinates for every
    layer's pool), so ``paged_model`` computes it once per forward and
    closes over it in the scanned layer body.

    Ownership invariant (why the full-block scatter back is safe): the
    touched window starts at ``start_pos//bs``, and every block at or past
    that index belongs exclusively to the writing sequence — prefix-cache
    sharing only ever covers *full* blocks strictly below the matched
    length (block-aligned), trims into indexed blocks are refused, and
    padding rows (``n_tokens == 0``) produce an empty window.
    """
    N, MB = block_tables.shape
    bs = block_size
    TB = (chunk + 2 * bs - 2) // bs
    ctx_len = start_pos + n_tokens                                   # [N]
    first_blk = start_pos // bs                                      # [N]
    tidx = first_blk[:, None] + jnp.arange(TB)[None, :]              # [N, TB]
    ids = jnp.take_along_axis(block_tables,
                              jnp.clip(tidx, 0, MB - 1), axis=1)     # [N, TB]
    touched = (tidx * bs < ctx_len[:, None]) & (tidx < MB) & (ids >= 0)
    # gather side clamps (garbage rows are masked below); scatter side
    # uses the sentinel NB: a *positive* out-of-range id, which
    # mode="drop" really drops (-1 would wrap to pool block NB-1 — JAX
    # normalizes negative scatter indices before the bounds check)
    gather_ids = jnp.where(touched, jnp.clip(ids, 0, num_blocks - 1), 0)
    scatter_ids = jnp.where(touched, ids, num_blocks)
    # live KV slots of each touched block: global position < ctx_len.
    # Slots past that hold stale content (freed tenant / trimmed drafts);
    # the quantized write zeroes them so they can neither inflate the
    # scale nor survive the re-quantized write-back.
    slot_pos = tidx[:, :, None] * bs + jnp.arange(bs)[None, None, :]
    live_slots = (slot_pos < ctx_len[:, None, None]) & touched[:, :, None]
    # which of the chunk's tokens each slot of the gathered [N, TB, ...]
    # view receives: the one at global position slot_pos, if the chunk
    # holds it
    src = slot_pos - start_pos[:, None, None]                    # [N, TB, bs]
    new_slots = (src >= 0) & (src < n_tokens[:, None, None]) \
        & touched[:, :, None]
    src_tok = jnp.clip(src, 0, chunk - 1).reshape(N, TB * bs)
    # blocks already holding this sequence's quantized tokens keep a
    # monotone scale; a freshly allocated block ignores the stale plane
    # entry of its previous tenant (the "scale invalidation on free")
    has_prior = (tidx * bs < start_pos[:, None]) & touched
    return {"gather_ids": gather_ids, "scatter_ids": scatter_ids,
            "live_slots": live_slots, "has_prior": has_prior,
            "new_slots": new_slots, "src_tok": src_tok}


def place_rows(blocks, new_vals, plan):
    """Step 2: ``new_vals`` [N*C, KH, D] (the chunk's rows, sequence-major)
    into the gathered view ``blocks`` [N, TB, KH, bs, D]: each slot takes
    the row ``plan`` names for it or keeps what it held. A headless pool's
    view is [N, TB, bs, W] and its rows [N*C, W]."""
    if blocks.ndim == 4:
        return place_rows(blocks[:, :, None], new_vals[:, None], plan)[:, :, 0]
    N, TB, KH, bs, D = blocks.shape
    rows = new_vals.reshape(N, -1, KH, D)
    if rows.shape[1] == 1:      # a decode token: every slot is offered it
        picked = jnp.broadcast_to(rows, (N, TB * bs, KH, D))
    else:
        picked = rows[jnp.arange(N)[:, None], plan["src_tok"]]
    picked = picked.reshape(N, TB, bs, KH, D).transpose(0, 1, 3, 2, 4)
    return jnp.where(plan["new_slots"][:, :, None, :, None],
                     picked.astype(blocks.dtype), blocks)


def block_write(pool, new_vals, plan, layer):
    """Write new K or V rows into layer ``layer`` of an unquantized pool
    [L, NB, KH, bs, D] — or latent rows [N*C, W] into a headless one
    [L, NB, bs, W] — (reference ``linear_blocked_kv_rotary`` kernel):
    token t lands at ``pool[layer, block(t), :, slot(t), :]``, every other
    slot keeps its content. Returns the updated pool — the same buffer
    when the caller owns it (the paged forward's scan carry)."""
    blocks = place_rows(pool[layer, plan["gather_ids"]], new_vals, plan)
    return pool.at[layer, plan["scatter_ids"]].set(blocks, mode="drop")
