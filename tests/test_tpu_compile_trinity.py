"""The cell ``trinity-large-preview.mixedctx``'s forwards compiled for a
*described* TPU v5e at the sizes its configuration's file states — the
weights of one leading dense layer and four sparse layers at the
published widths, both layer groups' pools at the file's sizes, the table
of 67,072 positions — for every chunk bucket ``[1, C]`` and the widest
decode step ``[S, 1]``: what the chip's compiler refuses, and what does
not fit beside the weights, shows here and not on the chip. Nothing runs.
The paged kernel at 6 query heads a KV head (a 1,024-token chunk is
6,144 query rows a KV head: cut in pieces of 256 tokens, a 2,048-token
chunk in eight), one call a
layer over that layer's group; the grouped matmul as the Pallas ``gmm``
at experts 3,072 wide; every pool leaf aliased to the output. How many
pieces every bucket is cut in is also held without compiling. See
tests/test_tpu_compile.py for the method and tests/tpu_compile_harness.py
for what is shared."""

import re

import pytest
from tpu_compile_harness import (_no_persistent_cache, bucket_id,  # noqa: F401
                                 configuration, fits_beside, kernels, lowered,
                                 stacked_group_sizes, staged_projections, v5e)

from deepspeed_tpu.models.mixers import attention, base
from deepspeed_tpu.ops import paged_attention as pa

NAME = "trinity-large-preview"
BUCKETS = [(1, 64), (1, 128), (1, 256), (1, 512), (1, 1024), (1, 2048),
           (32, 1)]


def _tile(cfg, C):
    """The tokens of one piece of a ``C``-token chunk."""
    return pa._chunk_tile(C, cfg.num_heads // cfg.kv_heads)


@pytest.mark.parametrize("bucket,tile,pieces", [
    ((1, 64), 64, 1), ((1, 128), 128, 1), ((1, 256), 256, 1),
    ((1, 512), 256, 2), ((1, 1024), 256, 4), ((1, 2048), 256, 8),
    ((32, 1), 1, 1)], ids=bucket_id)
def test_the_pieces_each_bucket_is_cut_in(bucket, tile, pieces):
    """Without compiling: the piece ``_chunk_tile`` gives each of the
    file's buckets at 6 query heads a KV head, by number -- so a crossing
    that moves shows by name."""
    cfg, sizes = configuration(NAME)
    assert cfg.num_heads // cfg.kv_heads == 6
    assert bucket[1] <= sizes["max_chunk_tokens"]
    assert _tile(cfg, bucket[1]) == tile
    assert bucket[1] // tile == pieces


@pytest.mark.parametrize("bucket", BUCKETS, ids=bucket_id)
def test_the_cells_forwards_at_the_files_sizes(v5e, bucket, monkeypatch):
    low, params, cache, cfg = lowered(NAME, v5e[0], bucket, monkeypatch)
    # the pools as the engine would size them (its rule, not a copy of it)
    assert cfg.kv_groups() == ((0, 1), (4096, 4))
    assert cache["k"].shape == cache["v"].shape == (1, 10240, 8, 64, 128)
    assert cache["k1"].shape == cache["v1"].shape == (4, 2176, 8, 64, 128)
    compiled = low.compile()
    text = compiled.as_text()
    found = kernels(text)
    # five attention layers; a chunk over MAX_QUERY_ROWS // 6 tokens is cut
    # in pieces that divide it
    C = bucket[1]
    tile = _tile(cfg, C)
    assert found.count("paged_attention") == 5 * (C // tile)
    if C >= 1024:
        assert tile == 256
    # gate, up, down in each of the four sparse layers, nothing of XLA's own
    assert found.count("gmm") == 12
    assert not any(k.startswith("ragged") for k in found)
    # one period deep: the experts are the period's own leaves, as ever
    assert cfg.num_periods == 1 and not stacked_group_sizes(text)
    scoped = re.findall(r'%paged_attention[.\d]* = [^\n]*op_name="([^"]*)"',
                        text)
    assert scoped and all("/attend/" in s and ("window_attn" in s
                                               or "full_attn" in s)
                          for s in scoped)
    assert sum("full_attn" in s for s in scoped) == C // tile
    # a bucket whose rows are few against the weights holds its five
    # attention layers' q, gate, k and v to rows (the lead layer's and the
    # scanned period's four slots' in the program's text) and stages none
    # of their weights in front of its dot; a wider one is the program it
    # was, with nothing held
    narrow = base.narrow(cfg, bucket[0] * bucket[1])
    assert narrow == (bucket[0] * C <= 768)
    assert low.as_text().count("@LayoutConstraint") == (20 if narrow else 0)
    if narrow:
        assert staged_projections(text, params) == []
    # weights + pools + this forward's temporaries fit the chip, with
    # room for the check's float32 reference (~2.5 GiB) when nothing runs
    fits_beside(compiled, params, cache, bucket, headroom=2 * 2 ** 30)


def test_left_free_a_step_copies_its_projections_weights(v5e, monkeypatch):
    """What the hold is for, and that ``staged_projections`` sees it: the
    cell's decode bucket ``[4, 1]`` with nothing held (the parent of
    PR 61: ``held`` made the identity) copies ``wq`` and ``wg`` of each of
    the five attention layers (37.7 MB each) and their ``wk`` and ``wv``
    (6.3 MB each), transposed, into fast memory in front of their dots --
    440 MB a forward of 4 ms. If this fails because nothing is staged, the
    compiler has learnt to leave them where they lie and the hold has
    lost its reason."""
    monkeypatch.setattr(attention, "held",
                        lambda cfg, always="": lambda name, y: y)
    low, params, *_ = lowered(NAME, v5e[0], (4, 1), monkeypatch)
    assert "@LayoutConstraint" not in low.as_text()
    staged = staged_projections(low.compile().as_text(), params)
    assert all(name.startswith("copy") for name, _, _ in staged)
    assert sorted(dims.removeprefix("1,") for _, dims, _ in staged) == \
        ["1024,3072"] * 10 + ["3072,6144"] * 5 + ["6144,3072"] * 5
    assert sum(size for _, _, size in staged) == 5 * 2 * (
        2 * 3072 * 6144 + 2 * 3072 * 1024)
