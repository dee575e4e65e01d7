"""A test double of a block type whose forward yields a block of tokens —
the contract's three optional names, over the ``dense`` reference (the
double's model is a causal one, so the plain reference answers every view).

Generation here: a block of ``W`` tokens behind the committed context, its
first the greedy draw of the last committed position, the rest the
placeholder ``MASK``. ``W - 1`` times the block is fed whole, the logits of
all its ``W`` rows read, the forward rolled back, and one more position
fixed from the row before it; then the finished block is fed once more
and stays. It lives in a file of its own (``tests/benchmark/twins/blocks/``)
and reaches a temporary checkout's ``benchmark/blocks/`` by copy, as a later
PR's block would be added: no file that is there is edited."""

import numpy as np

from benchmark.blocks import dense

W, MASK = 4, 0

logits, loss, matmul_params = dense.logits, dense.loss, dense.matmul_params


def _read_and_roll_back(engine, uid, block):
    rows = engine.put([uid], [block], verify_width=len(block),
                      defer_commit=True)
    got = np.asarray(rows, np.float32)[0]           # [W, vocab]
    engine.trim_sequence(uid, len(block))
    return got


def replay(engine, uid, prompt, decode_steps):
    """A step is one block of ``W`` tokens: ``W - 1`` forwards that read
    every row and are rolled back, a view each, and the forward that
    commits the block, whose last row is a view. The prompt's last row is
    the first view: ``1 + decode_steps * W`` views a prompt."""
    chunk = engine.config.max_chunk_tokens
    for at in range(0, len(prompt), chunk):
        out = engine.put([uid], [prompt[at:at + chunk]])
    last = np.asarray(out[0], np.float32)
    tokens = list(prompt)
    views = [(list(tokens), [len(tokens) - 1], last[None])]
    for _ in range(decode_steps):
        at, block = len(tokens), [int(np.argmax(last))] + [MASK] * (W - 1)
        for undecided in range(1, W):
            got = _read_and_roll_back(engine, uid, block)
            views.append((tokens + block, range(at, at + W), got))
            block[undecided] = int(np.argmax(got[undecided - 1]))
        last = np.asarray(engine.put([uid], [block])[0], np.float32)
        tokens += block
        views.append((list(tokens), [len(tokens) - 1], last[None]))
    return views


def warm_up(engine, uids):
    """The one program the replay reaches and the ``[S, C]`` grid does
    not: a ``[1, W]`` forward that returns all ``W`` rows."""
    uid = next(uids)
    _read_and_roll_back(engine, uid, [MASK] * W)
    engine.flush(uid)
    return 1


def qk_pairs(new, seen):
    """Under a block-causal mask a position sees its whole block: every
    one of a row's ``new`` positions sees the ``seen`` and all ``new``
    (rows end on block edges here)."""
    return int((new * (seen + new)).sum())
