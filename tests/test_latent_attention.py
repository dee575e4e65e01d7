"""The two latent-attention kernels (ops/latent_attention.py) in the
Pallas interpreter against their XLA twins and a dense masked softmax:
the absorbed one over rows of one query position (contexts that end
inside a block, an empty row, several loop turns), the expanded one over
chunks that start at 0, inside and at the end of earlier context (one
tile and several, blocks the diagonal crosses and blocks it does not)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import latent_attention as la

H, R, ROPE, W, BS, NB, L = 4, 128, 32, 256, 16, 40, 2
DN, DV, MB = 128, 64, 8
SCALE = 0.1


@pytest.fixture()
def pool():
    rng = np.random.default_rng(0)
    rows = np.zeros((L, NB, BS, W), np.float32)
    rows[..., :R + ROPE] = rng.normal(size=(L, NB, BS, R + ROPE))
    tables = rng.permutation(NB)[:3 * MB].reshape(3, MB)
    return jnp.asarray(rows), jnp.asarray(tables, jnp.int32)


@pytest.fixture()
def interpret(monkeypatch):
    monkeypatch.setattr(la, "_FORCE_INTERPRET", True)


@pytest.mark.parametrize("key_tile", [16, 32, 512])
def test_absorbed_kernel_against_its_twin_and_a_dense_softmax(
        pool, interpret, monkeypatch, key_tile):
    rows, tables = pool
    monkeypatch.setattr(la, "KEY_TILE", key_tile)
    rng = np.random.default_rng(1)
    q = np.zeros((3, H, W), np.float32)
    q[..., :R + ROPE] = rng.normal(size=(3, H, R + ROPE))
    q = jnp.asarray(q)
    ctx = jnp.asarray([37, 0, 128], jnp.int32)
    got = la.latent_decode(q, rows, 1, tables, ctx, R, SCALE)
    twin = la.latent_decode_xla(q, rows, 1, tables, ctx, R, SCALE)
    assert got.shape == (3, H, R)
    np.testing.assert_allclose(got, twin, atol=2e-6)
    assert not np.asarray(got[1]).any()             # the empty row
    kv = np.asarray(rows[1, tables[0]]).reshape(-1, W)[:37]
    s = np.einsum("hw,sw->hs", np.asarray(q[0]), kv) * SCALE
    p = np.exp(s - s.max(-1, keepdims=True))
    want = (p / p.sum(-1, keepdims=True)) @ kv[:, :R]
    np.testing.assert_allclose(got[0], want, atol=2e-5)


def _expand(wkb, wvb):
    return lambda c: (jnp.einsum("tc,chd->htd", c, wkb),
                      jnp.einsum("tc,chd->htd", c, wvb))


@pytest.mark.parametrize("start, n", [(0, 32), (50, 20), (96, 32), (5, 1)],
                         ids=["fresh", "inside", "to-the-end", "one-token"])
@pytest.mark.parametrize("tile, bq, bk", [(64, 16, 32), (128, 32, 128),
                                          (32, 32, 16)])
def test_expanded_kernel_against_its_twin_and_a_dense_softmax(
        pool, monkeypatch, start, n, tile, bq, bk):
    rows, tables = pool
    monkeypatch.setattr(la, "EXPAND_TILE", tile)
    monkeypatch.setattr(la, "BLOCK_Q", bq)
    monkeypatch.setattr(la, "BLOCK_K", bk)
    rng = np.random.default_rng(2)
    C = 32
    wkb = jnp.asarray(rng.normal(size=(R, H, DN)) * 0.1, jnp.float32)
    wvb = jnp.asarray(rng.normal(size=(R, H, DV)) * 0.1, jnp.float32)
    qn = jnp.asarray(rng.normal(size=(C, H, DN)), jnp.float32)
    qr = jnp.asarray(rng.normal(size=(C, H, ROPE)), jnp.float32)
    expand = _expand(wkb, wvb)

    def run(force):
        monkeypatch.setattr(la, "_FORCE_INTERPRET", force)
        return jax.jit(lambda: la.latent_prefill(
            qn, qr, rows, 1, tables[0], jnp.asarray(start), jnp.asarray(n),
            expand, R, DV, SCALE))()

    kernel, twin = run(True), run(False)
    assert kernel.shape == (C, H, DV)
    lat = rows[1, tables[0]].reshape(-1, W)
    kn, v = expand(lat[:, :R])
    s = (jnp.einsum("chd,htd->hct", qn, kn)
         + jnp.einsum("chd,td->hct", qr, lat[:, R:R + ROPE])) * SCALE
    qpos = start + jnp.arange(C)[:, None]
    kpos = jnp.arange(lat.shape[0])[None]
    keep = (kpos <= qpos) & (kpos < start + n)
    want = jnp.einsum("hct,htd->chd",
                      jax.nn.softmax(jnp.where(keep[None], s, -1e30), -1), v)
    np.testing.assert_allclose(kernel[:n], want[:n], atol=5e-6)
    np.testing.assert_allclose(twin[:n], want[:n], atol=5e-6)


def test_the_tile_and_what_a_chunk_rebuilds():
    assert la.expand_tile(528, 64) == 4096          # the cell's table
    assert la.expand_tile(32, 8) == 256             # a short table, whole
    assert la.expand_positions(0, 2048, 4096) == 4096
    assert la.expand_positions(6144, 2048, 4096) == 8192
    assert la.expand_positions(100, 0, 4096) == 0
    # a prompt of 12,000 in chunks of 2,048: its contexts in whole tiles
    chunks = [(at, min(2048, 12000 - at)) for at in range(0, 12000, 2048)]
    assert sum(la.expand_positions(s, n, 4096) for s, n in chunks) \
        == 4096 * (1 + 1 + 2 + 2 + 3 + 3)


def test_off_the_tpu_the_twins_run_and_misaligned_widths_fall_to_them(
        pool, monkeypatch):
    rows, tables = pool
    calls = []
    monkeypatch.setattr(la, "_decode_pallas",
                        lambda *a: calls.append(a) or None)
    q = jnp.ones((1, H, W))
    ctx = jnp.asarray([9], jnp.int32)
    assert la.latent_decode(q, rows, 0, tables[:1], ctx, R, SCALE) \
        is not None and not calls
    monkeypatch.setattr(la, "_FORCE_INTERPRET", True)
    # a rank that is not whole lane tiles cannot be sliced off the rows
    la.latent_decode(q, rows, 0, tables[:1], ctx, R - 8, SCALE)
    assert not calls
    la.latent_decode(q, rows, 0, tables[:1], ctx, R, SCALE)
    assert len(calls) == 1
