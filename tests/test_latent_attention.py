"""The two latent-attention kernels (ops/latent_attention.py) in the
Pallas interpreter against their XLA twins and a dense masked softmax:
the absorbed one over rows of one query position (contexts that end
inside a block, an empty row, several loop turns), the expanded one over
chunks that start at 0, inside and at the end of earlier context (one
tile and several, blocks the diagonal crosses and blocks it does not)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

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


# ------------------------------------------------ a window and a selection

def _dense(q_rows, kv, keep, rank=R):
    """Softmax of ``q_rows`` [H, W] over the rows ``kv`` [S, W] that
    ``keep`` [S] names, times their first ``rank`` lanes."""
    s = np.einsum("hw,sw->hs", np.asarray(q_rows), kv) * SCALE
    s = np.where(keep[None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return (p / p.sum(-1, keepdims=True)) @ kv[:, :rank]


@pytest.mark.parametrize("window", [5, 16, 40, 300])
def test_absorbed_kernel_under_a_window_walks_from_its_first_block(
        pool, interpret, window):
    rows, tables = pool
    rng = np.random.default_rng(3)
    q = np.zeros((3, H, W), np.float32)
    q[..., :R + ROPE] = rng.normal(size=(3, H, R + ROPE))
    q = jnp.asarray(q)
    ctx = jnp.asarray([37, 0, 128], jnp.int32)
    # what lies wholly behind the window is gone from the table
    gone = np.asarray(tables).copy()
    for n, c in enumerate([37, 0, 128]):
        gone[n, :max(c - window, 0) // BS] = -1
    got = la.latent_decode(q, rows, 1, jnp.asarray(gone), ctx, R, SCALE,
                           window=window)
    twin = la.latent_decode_xla(q, rows, 1, tables, ctx, R, SCALE, window)
    np.testing.assert_allclose(got, twin, atol=2e-6)
    assert not np.asarray(got[1]).any()
    for n, c in ((0, 37), (2, 128)):
        kv = np.asarray(rows[1, tables[n]]).reshape(-1, W)
        at = np.arange(kv.shape[0])
        np.testing.assert_allclose(
            got[n], _dense(q[n], kv, (at < c) & (at >= c - window)),
            atol=2e-5)


@pytest.mark.parametrize("start, n", [(0, 32), (50, 20), (96, 32)],
                         ids=["fresh", "inside", "to-the-end"])
@pytest.mark.parametrize("mode", ["window", "selected", "joined"])
def test_expanded_kernel_under_a_window_a_mask_and_a_joined_rope(
        pool, monkeypatch, start, n, mode):
    """``window``: keys within 24 of the query, the loop starting at the
    window's tile; ``selected``: each query's own random subset; ``joined``:
    a nope width of 96 with the 32-wide rope behind it, one dot."""
    rows, tables = pool
    monkeypatch.setattr(la, "EXPAND_TILE", 64)
    monkeypatch.setattr(la, "BLOCK_Q", 16)
    monkeypatch.setattr(la, "BLOCK_K", 32)
    rng = np.random.default_rng(4)
    C, dn = 32, 96 if mode == "joined" else DN
    window = 24 if mode != "selected" else 0
    wkb = jnp.asarray(rng.normal(size=(R, H, dn)) * 0.1, jnp.float32)
    wvb = jnp.asarray(rng.normal(size=(R, H, DV)) * 0.1, jnp.float32)
    qn = jnp.asarray(rng.normal(size=(C, H, dn)), jnp.float32)
    qr = jnp.asarray(rng.normal(size=(C, H, ROPE)), jnp.float32)
    expand = _expand(wkb, wvb)
    keys = MB * BS
    picked = jnp.asarray(rng.random((C, keys)) < 0.4) \
        if mode == "selected" else None
    options = {"window": window} if window else {}
    if picked is not None:
        options["keep"] = picked.astype(jnp.int8)

    def run(force):
        monkeypatch.setattr(la, "_FORCE_INTERPRET", force)
        return jax.jit(lambda: la.latent_prefill(
            qn, qr, rows, 1, tables[0], jnp.asarray(start), jnp.asarray(n),
            expand, R, DV, SCALE, **options))()

    kernel, twin = run(True), run(False)
    lat = rows[1, tables[0]].reshape(-1, W)
    kn, v = expand(lat[:, :R])
    s = (jnp.einsum("chd,htd->hct", qn, kn)
         + jnp.einsum("chd,td->hct", qr, lat[:, R:R + ROPE])) * SCALE
    qpos = start + jnp.arange(C)[:, None]
    kpos = jnp.arange(keys)[None]
    keep = (kpos <= qpos) & (kpos < start + n)
    if window:
        keep &= kpos > qpos - window
    if picked is not None:
        # a query with no selected key at all has no answer to hold
        keep &= picked
        rows_ok = np.asarray(keep.any(-1))[:n]
    else:
        rows_ok = np.ones((n,), bool)
    want = jnp.einsum("hct,htd->chd",
                      jax.nn.softmax(jnp.where(keep[None], s, -1e30), -1), v)
    np.testing.assert_allclose(kernel[:n][rows_ok], want[:n][rows_ok],
                               atol=5e-6)
    np.testing.assert_allclose(twin[:n][rows_ok], want[:n][rows_ok],
                               atol=5e-6)


@pytest.mark.parametrize("queries", [1, 8])
def test_index_score_kernel_against_its_twin_and_the_definition(
        interpret, monkeypatch, queries):
    monkeypatch.setattr(la, "KEY_TILE", 32)
    rng = np.random.default_rng(5)
    hi, D, N = 8, 128, 3
    pool = jnp.asarray(rng.normal(size=(L, NB, BS, D)), jnp.float32)
    tables = jnp.asarray(rng.permutation(NB)[:N * MB].reshape(N, MB),
                         jnp.int32)
    q = jnp.asarray(rng.normal(size=(N, queries * hi, D)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(N, queries * hi)), jnp.float32)
    ctx = jnp.asarray([37, 0, 128], jnp.int32)
    got = la.index_score(q, w, pool, 1, tables, ctx, queries)
    twin = la.index_score_xla(q, w, pool, 1, tables, ctx, queries)
    assert got.shape == twin.shape == (N, queries, MB * BS)
    for n, c in ((0, 37), (2, 128)):
        np.testing.assert_allclose(got[n, :, :c], twin[n, :, :c], atol=2e-4)
        k = np.asarray(pool[1, tables[n]]).reshape(-1, D)[:c]
        qh = np.asarray(q[n]).reshape(queries, hi, D)
        want = np.einsum("qhs,qh->qs",
                         np.maximum(np.einsum("qhd,sd->qhs", qh, k), 0),
                         np.asarray(w[n]).reshape(queries, hi))
        np.testing.assert_allclose(got[n, :, :c], want, atol=2e-4)


def _selection_case(name):
    """-> (scores, live, topk) of one named case of the selection."""
    rng = np.random.default_rng(len(name))

    def prefix(ctx, S):
        return np.arange(S) < np.asarray(ctx)[..., None]

    if name == "fewer_live_than_k":
        return rng.normal(size=(3, 700)), prefix([1, 40, 63], 700), 64
    if name == "exactly_k_live":
        return rng.normal(size=(2, 700)), prefix([64, 64], 700), 64
    if name == "width_under_k":
        return rng.normal(size=(2, 300)), prefix([300, 17], 300), 2048
    if name == "all_equal":
        return np.full((2, 600), 0.25), prefix([600, 90], 600), 64
    if name == "ties_straddle_the_edge":
        # a handful of levels: the 64th largest is one of many equal keys
        return (np.round(rng.normal(size=(4, 900)) * 2) / 2,
                prefix([900, 500, 70, 257], 900), 64)
    if name == "inf_negative_denormal":
        s = -np.abs(rng.normal(size=(3, 520)))
        s[0, ::3] = -np.inf
        s[1] = rng.integers(-40, 40, size=520) * 1e-42   # denormals
        s[2, :100] = np.inf
        return s, prefix([520, 400, 300], 520), 200
    if name == "live_is_no_prefix":
        return (np.round(rng.normal(size=(3, 800)), 1),
                rng.random(size=(3, 800)) < [[0.5], [0.05], [0.9]], 64)
    if name == "leading_axes":
        return (rng.normal(size=(2, 3, 2, 400)),
                prefix(rng.integers(1, 401, size=(2, 3, 2)), 400), 50)
    if name == "width_16384":
        return (np.round(rng.normal(size=(2, 16384)), 2),
                prefix([16384, 9000], 16384), 2048)
    if name == "width_no_multiple_of_the_block":
        return (rng.normal(size=(2, 2 * 256 + 77)),
                prefix([2 * 256 + 77, 300], 2 * 256 + 77), 100)
    raise KeyError(name)


def _parents_index_keep(scores, live, topk):
    """``hybrid.index_keep`` as PR 43 wrote it, before it shared the
    threshold with ``index_select``."""
    k = min(int(topk), scores.shape[-1])
    bits = lax.bitcast_convert_type(
        jnp.where(live, scores, -jnp.inf).astype(jnp.float32), jnp.uint32)
    top = jnp.uint32(1 << 31)
    key = jnp.where(bits >= top, ~bits, bits | top)

    def refine(i, kth):
        cand = kth | (top >> i.astype(jnp.uint32))
        enough = jnp.sum(key >= cand[..., None], axis=-1,
                         dtype=jnp.int32) >= k
        return jnp.where(enough, cand, kth)

    kth = lax.fori_loop(0, 32, refine,
                        jnp.zeros(scores.shape[:-1], jnp.uint32))
    return live & (key >= kth[..., None])


@pytest.mark.parametrize("case", [
    "fewer_live_than_k", "exactly_k_live", "width_under_k", "all_equal",
    "ties_straddle_the_edge", "inf_negative_denormal", "live_is_no_prefix",
    "leading_axes", "width_16384", "width_no_multiple_of_the_block"])
def test_the_selection_is_lax_top_ks_set_and_holds_no_sort(case):
    """``hybrid.index_select`` against ``lax.top_k`` over the same masked
    scores: the same ``n``, the same set in its first ``n`` entries (of
    equal scores at the edge the earlier positions), ascending, the rest
    0 — and the mask ``index_keep`` makes of the same threshold is the
    parent's, bit for bit."""
    from deepspeed_tpu.models import hybrid

    scores, live, topk = _selection_case(case)
    scores, live = jnp.asarray(scores, jnp.float32), jnp.asarray(live)
    k = min(topk, scores.shape[-1])
    select = jax.jit(hybrid.index_select,
                     static_argnums=2).lower(scores, live, topk)
    assert not re.search(r"\bsort\b|top_k|\bscatter\b", select.as_text())
    idx, n = map(np.asarray, select.compile()(scores, live))
    assert idx.shape == scores.shape[:-1] + (k,) and n.shape == idx.shape[:-1]
    live_n = np.asarray(live).sum(-1)
    np.testing.assert_array_equal(n, np.minimum(live_n, k))
    # a dead key sorts behind every live one (-inf, and a later position
    # than the live -inf ones where the case has them: live is a prefix)
    _, want = lax.top_k(jnp.where(live, scores, -jnp.inf), k)
    want = np.asarray(want).reshape(-1, k)
    flat_live = np.asarray(live).reshape(-1, live.shape[-1])
    for row, (got, ref, m) in enumerate(zip(idx.reshape(-1, k), want,
                                            n.reshape(-1))):
        assert set(got[:m]) == set(ref[:m]), row
        assert (np.diff(got[:m]) > 0).all() and not got[m:].any(), row
        assert flat_live[row, got[:m]].all(), row
    np.testing.assert_array_equal(
        np.asarray(hybrid.index_keep(scores, live, topk)),
        np.asarray(_parents_index_keep(scores, live, topk)))


def test_sparse_absorbed_kernel_attends_the_gathered_rows_and_no_other(
        pool, interpret):
    rows, tables = pool
    rng = np.random.default_rng(6)
    K = 32
    q = np.zeros((3, H, W), np.float32)
    q[..., :R + ROPE] = rng.normal(size=(3, H, R + ROPE))
    q = jnp.asarray(q)
    ctx = [37, 0, 128]
    idx = np.stack([np.r_[rng.permutation(max(c, 1))[:K],
                          np.zeros(max(K - c, 0), int)][:K] for c in ctx])
    n_sel = jnp.asarray([min(c, K) for c in ctx], jnp.int32)
    got = la.latent_sparse_decode(q, rows, 1, tables, jnp.asarray(idx),
                                  n_sel, R, SCALE)
    kv = rows[1, jnp.take_along_axis(tables, jnp.asarray(idx) // BS, 1),
              jnp.asarray(idx) % BS]
    np.testing.assert_allclose(
        got, la.sparse_decode_xla(q, kv, n_sel, R, SCALE), atol=2e-6)
    assert not np.asarray(got[1]).any()
    for n in (0, 2):
        lat = np.asarray(rows[1, tables[n]]).reshape(-1, W)
        keep = np.zeros(lat.shape[0], bool)
        keep[idx[n, :int(n_sel[n])]] = True
        np.testing.assert_allclose(got[n], _dense(q[n], lat, keep), atol=2e-5)


def test_where_a_kinds_paths_cross_is_a_function_of_its_widths():
    # R 512, nope 128, v 128: 33.6 M a key over 196.6 k a pair = 171 rows
    assert la.absorb_max_queries(512, 128, 64, 128) == la.ABSORB_MAX_QUERIES
    # R 1024, nope 192, v 128 under a window of 513: 300 rows
    assert la.absorb_max_queries(1024, 192, 64, 128, 513) == 256
    # a window whose own rebuild costs more than absorbing it: never
    assert la.absorb_max_queries(1024, 192, 64, 128, 64) >= 1 << 20
    assert la.expand_tile(1040, 64, 513) == 1024
    assert la.expand_tile(1040, 64) == 4096
