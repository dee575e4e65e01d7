"""The cell ``smallthinker-21b-a3b.bulkgen``'s forwards compiled for a
*described* TPU v5e at the sizes its configuration's file states — the
weights of two whole periods at the published widths (all 64 experts a
layer, the whole 151,936-row head), both layer groups' pools at the
file's sizes, the table of 11,264 positions — for the decode step
``[32, 1]`` and the widest chunk ``[1, 2048]``: what the chip's compiler
refuses, and what does not fit beside the weights, shows here and not on
the chip. Nothing runs. The paged kernel at 7 query heads a K/V head —
the first odd group among the cells: a one-token step's query tile is 7
rows a K/V head, a 2,048-token chunk is cut in pieces of 256 tokens
(1,792 query rows) — one call a layer over that layer's group; the
grouped matmul as the Pallas ``gmm`` at the benchmark's smallest expert
(768 wide), each call on the whole stack of both periods' experts, 128
groups, with the period's group sizes at its offset -- no copy of an
expert leaf out of its stack in front of a call; the router's matmul
ahead of the attention norm; every pool leaf aliased to the output. See
tests/test_tpu_compile.py for the method and
tests/tpu_compile_harness.py for what is shared."""

import re

import pytest
from tpu_compile_harness import (_no_persistent_cache, bucket_id,  # noqa: F401
                                 configuration, fits_beside, kernels, lowered,
                                 stacked_group_sizes, staged_projections, v5e)

from deepspeed_tpu.moe.grouped import gmm_tiles
from deepspeed_tpu.ops import paged_attention as pa

NAME = "smallthinker-21b-a3b"
BUCKETS = [(32, 1), (1, 2048)]


@pytest.mark.parametrize("bucket,tile,pieces", [
    ((32, 1), 1, 1), ((1, 64), 64, 1), ((1, 256), 256, 1),
    ((1, 512), 256, 2), ((1, 2048), 256, 8)], ids=bucket_id)
def test_the_pieces_each_bucket_is_cut_in(bucket, tile, pieces):
    """Without compiling: the piece ``_chunk_tile`` gives the file's
    buckets at 7 query heads a K/V head, and the weight tiles of the
    grouped matmul at an expert 768 wide."""
    cfg, sizes = configuration(NAME)
    assert cfg.num_heads // cfg.kv_heads == 7
    assert bucket[1] <= sizes["max_chunk_tokens"]
    assert pa._chunk_tile(bucket[1], 7) == tile
    assert bucket[1] // tile == pieces
    assert gmm_tiles(2560, 768) == (1280, 768)
    assert gmm_tiles(768, 2560) == (768, 1280)


@pytest.mark.parametrize("bucket", BUCKETS, ids=bucket_id)
def test_the_cells_forwards_at_the_files_sizes(v5e, bucket, monkeypatch):
    low, params, cache, cfg = lowered(NAME, v5e[0], bucket, monkeypatch)
    # the pools as the engine would size them (its rule, not a copy of it)
    assert cfg.kv_groups() == ((0, 2), (4096, 6))
    assert cache["k"].shape == cache["v"].shape == (2, 6144, 4, 64, 128)
    assert cache["k1"].shape == cache["v1"].shape == (6, 2176, 4, 64, 128)
    compiled = low.compile()
    text = compiled.as_text()
    found = kernels(text)
    # the scan's body holds one period: four attention layers, a chunk
    # over MAX_QUERY_ROWS // 7 tokens cut in pieces that divide it
    C = bucket[1]
    tile = pa._chunk_tile(C, 7)
    assert found.count("paged_attention") == 4 * (C // tile)
    # gate, up, down in each of the period's four layers, nothing of XLA's
    assert found.count("gmm") == 12
    assert not any(k.startswith("ragged") for k in found)
    # each reads its experts where they lie: the operand is the stack of
    # both periods (a bitcast of the parameter, carried by the loop), the
    # period's group sizes sit at its offset among 128, and nothing in the
    # module is one period's expert leaf (a leaf that rides the scan's xs
    # is written out of its stack, 240 MiB, in front of its call)
    stacks = re.findall(
        r"%gmm[.\d]* = [^\n]*?(%[\w.\-]+)\), custom_call", text)
    assert len(stacks) == 12
    for operand in set(stacks):
        made = re.search(re.escape(operand) + r" = (\S+) (\S+?)\(", text)
        assert made.group(1).startswith(("bf16[128,2560,768]",
                                         "bf16[128,768,2560]"))
        assert made.group(2) == "get-tuple-element"
    assert not re.search(r"bf16\[64,(2560,768|768,2560)\]", text)
    assert stacked_group_sizes(text)
    scoped = re.findall(r'%paged_attention[.\d]* = [^\n]*op_name="([^"]*)"',
                        text)
    assert scoped and all("/attend/" in s and ("window_attn" in s
                                               or "full_attn" in s)
                          for s in scoped)
    assert sum("full_attn" in s for s in scoped) == C // tile
    # the router reads the layer's input: its scope is a sibling of
    # attn_norm, not a part of mlp
    names = set(re.findall(r'op_name="([^"]*)"', text))
    assert any("/router/" in n for n in names)
    assert not any("mlp/router" in n for n in names)
    # weights + pools + this forward's temporaries fit the chip, with
    # room for the check's float32 reference when nothing runs (2.46 GiB
    # of logits at 4,352 positions and what they are made from)
    fits_beside(compiled, params, cache, bucket, headroom=4 * 2 ** 30)
    # a decode step's temporaries hold no expert leaf (240 MiB), and it
    # holds a period's four slots' q, k and v to rows: no projection's
    # weight is sliced out of its stack and copied, transposed, in front
    # of its dot (``mixers.base.held``; left free: 95.6 MB a period). The
    # widest chunk holds nothing and is the program it was
    held = low.as_text().count("@LayoutConstraint")
    if C == 1:
        assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2 ** 20
        assert held == 12 and staged_projections(text, params) == []
    else:
        assert held == 0
