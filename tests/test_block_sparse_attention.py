"""The two attention calls of a block-sparse layer
(``ops/paged_attention.py``): ``paged_attention_select`` — one-token rows
over a table of pool blocks a K/V head, the row's own block last — and
``paged_attention_masked`` — chunk rows under a mask of table blocks a
query position a K/V head. The Pallas bodies, interpreted, against their
XLA formulations, and both against plain attention over the keys the
selection names; the selection's exact mask (``hybrid.index_kept``)
against ``index_select``'s set."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import hybrid
from deepspeed_tpu.ops import paged_attention as pa

L, NB, KH, BS, D, H = 2, 40, 2, 8, 16, 8


@pytest.fixture(scope="module")
def pools():
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    return (jax.random.normal(k1, (L, NB, KH, BS, D)),
            jax.random.normal(k2, (L, NB, KH, BS, D)))


def _both(call):
    """``call()`` on the XLA path and on the interpreted kernel."""
    out = []
    for force in (False, True):
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(pa, "_FORCE_INTERPRET", force)
            out.append(np.asarray(call()))
    return out


def _plain(q, k, v):
    """softmax(q k^T / sqrt(D)) v for q [G, D] over k, v [S, D]."""
    p = jax.nn.softmax((q @ k.T) / np.sqrt(D), axis=-1)
    return np.asarray(p @ v)


def test_one_token_rows_read_their_selected_blocks_and_no_other(pools):
    kp, vp = pools
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    N, W = 3, 6
    q = jax.random.normal(ks[0], (N, 1, H, D))
    tables = jax.random.randint(ks[1], (N, KH, W), 0, NB)
    n_blocks, positions = jnp.asarray([4, 0, 6]), jnp.asarray([29, 0, 77])
    xla, kernel = _both(lambda: pa.paged_attention_select(
        q, kp, vp, tables, n_blocks, positions, layer=1))
    real = [0, 2]                       # row 1 is a padded one
    assert np.abs(xla[real] - kernel[real]).max() < 1e-6
    G = H // KH
    for n in real:
        seen = (int(n_blocks[n]) - 1) * BS + int(positions[n]) % BS + 1
        for h in range(KH):
            ids = np.asarray(tables[n, h, :n_blocks[n]])
            k = np.asarray(kp[1, ids, h]).reshape(-1, D)[:seen]
            v = np.asarray(vp[1, ids, h]).reshape(-1, D)[:seen]
            want = _plain(np.asarray(q[n, 0, h * G:(h + 1) * G]), k, v)
            assert np.abs(kernel[n, 0, h * G:(h + 1) * G] - want
                          ).max() < 1e-5
    # an entry past a row's count is never dereferenced: another table
    # there, the same answer
    other = tables.at[0, :, 4:].set(NB - 1)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(pa, "_FORCE_INTERPRET", True)
        assert (np.asarray(pa.paged_attention_select(
            q, kp, vp, other, n_blocks, positions, layer=1))[0]
            == kernel[0]).all()


def test_chunk_rows_attend_under_the_block_mask(pools):
    kp, vp = pools
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    N, C, MB = 2, 16, 13
    q = jax.random.normal(ks[0], (N, C, H, D))
    table = jax.random.permutation(ks[1], NB)[:N * MB].reshape(N, MB)
    start, n = jnp.asarray([40, 70]), jnp.asarray([16, 11])
    cur = (start[:, None] + jnp.arange(C)[None]) // BS
    own = jnp.arange(MB)[None, None, None, :] == cur[:, :, None, None]
    mask = ((jax.random.uniform(ks[2], (N, C, KH, MB)) > 0.5) | own
            ).astype(jnp.int8)
    xla, kernel = _both(lambda: pa.paged_attention_masked(
        q, kp, vp, table, start, n, mask, layer=0))
    valid = np.arange(C)[None] < np.asarray(n)[:, None]
    assert np.abs(xla - kernel)[valid].max() < 1e-6
    G = H // KH
    for row, c, h in ((0, 0, 0), (0, 15, 1), (1, 10, 0)):
        t = int(start[row]) + c
        blocks = np.flatnonzero(np.asarray(mask[row, c, h]))
        blocks = blocks[blocks <= t // BS]
        at = (blocks[:, None] * BS + np.arange(BS)[None]).reshape(-1)
        ids = np.asarray(table[row])[blocks]
        k = np.asarray(kp[0, ids, h]).reshape(-1, D)[at <= t]
        v = np.asarray(vp[0, ids, h]).reshape(-1, D)[at <= t]
        want = _plain(np.asarray(q[row, c, h * G:(h + 1) * G]), k, v)
        assert np.abs(kernel[row, c, h * G:(h + 1) * G] - want).max() < 1e-5
    # every block kept is the plain kernel; an unselected block attended
    # is another answer
    plain = np.asarray(pa.paged_attention(q, kp, vp, table, start, n,
                                          layer=0))
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(pa, "_FORCE_INTERPRET", True)
        ones = np.asarray(pa.paged_attention_masked(
            q, kp, vp, table, start, n, jnp.ones_like(mask), layer=0))
    assert np.abs(plain - ones)[valid].max() < 1e-6
    assert np.abs(ones - kernel)[valid].max() > 1e-2


@pytest.mark.parametrize("topk", [1, 3, 7, 300])
def test_the_exact_mask_is_the_exact_tables_set(topk):
    """``index_kept`` (ties at the edge to the earlier positions, to
    ``topk`` exactly) against ``index_select`` on scores full of ties."""
    rng = np.random.default_rng(topk)
    scores = jnp.asarray(rng.integers(0, 5, size=(4, 3, 260)), jnp.float32)
    live = jnp.asarray(rng.random((4, 3, 260)) > 0.3)
    mask = np.asarray(hybrid.index_kept(scores, live, topk))
    idx, n = map(np.asarray, hybrid.index_select(scores, live, topk))
    assert (n == np.minimum(np.asarray(live).sum(-1), topk)).all()
    for a in range(4):
        for b in range(3):
            assert set(np.flatnonzero(mask[a, b])) \
                == set(idx[a, b, :n[a, b]])
