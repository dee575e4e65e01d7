"""The hybrid serving forward compiled for a *described* TPU v5e at
Qwen3-Next-80B-A3B's widths (one period of four layers, a pool and slots
cut small — nothing runs, so sizes that only fill memory do not matter):
what the chip's compiler refuses shows here and not on the chip. The
paged kernel at head size 256 with 8 query heads a KV head, a 1,024-token
chunk cut into pieces of ``MAX_QUERY_ROWS`` rows; the grouped matmul as
the Pallas ``gmm`` (XLA's own ``ragged-dot`` custom calls carry no
``op_name``); every cache leaf — pool and recurrent state — aliased to
the output. See tests/test_tpu_compile.py for the method and
tests/tpu_compile_harness.py for what is shared; how many pieces either
bucket is cut in is also held without compiling."""

import re

import pytest
from tpu_compile_harness import (_no_persistent_cache, bucket_id,  # noqa: F401
                                 configuration, kernels, lowered, nbytes,
                                 stacked_group_sizes, staged_projections, v5e)

from deepspeed_tpu.ops import paged_attention as pa

NAME = "qwen3-next-80b-a3b"
BUCKETS = [(1, 1024), (8, 1)]


def _pieces(cfg, C):
    return C // pa._chunk_tile(C, cfg.num_heads // cfg.kv_heads)


@pytest.mark.parametrize("bucket,pieces", [((1, 1024), 4), ((8, 1), 1)],
                         ids=bucket_id)
def test_the_pieces_each_bucket_is_cut_in(bucket, pieces):
    """Without compiling: 8 query heads a KV head put 256 tokens in a
    piece of ``MAX_QUERY_ROWS`` rows."""
    cfg, sizes = configuration(NAME)
    assert cfg.num_heads // cfg.kv_heads == 8 and cfg.head_dim == 256
    assert bucket[1] <= sizes["max_chunk_tokens"]
    assert _pieces(cfg, bucket[1]) == pieces


@pytest.mark.parametrize("bucket", BUCKETS, ids=bucket_id)
def test_hybrid_forward_at_published_widths(v5e, bucket, monkeypatch):
    # the configuration's one period at its widths; 1,024 blocks, a
    # budget of 1,056 tokens and five slots
    low, params, cache, cfg = lowered(
        NAME, v5e[0], bucket, monkeypatch, kv_blocks=1024,
        max_ragged_batch_size=1056, max_ragged_sequence_count=8)
    assert cfg.num_layers == 4
    assert cache["k"].shape == cache["v"].shape == (1, 1024, 2, 64, 256)
    compiled = low.compile()
    text = compiled.as_text()
    found = kernels(text)
    # one attention layer: the chunk's 8 x 1024 query rows in four pieces
    assert found.count("paged_attention") == _pieces(cfg, bucket[1])
    # gate, up, down in each of the four layers, and nothing of XLA's own
    assert found.count("gmm") == 12
    assert not any(k.startswith("ragged") for k in found)
    scoped = re.findall(r'%gmm[.\d]* = [^\n]*op_name="([^"]*)"', text)
    assert scoped and all("/mlp/experts/" in s for s in scoped)
    # one period deep: the scan is unrolled, its slice of an expert leaf
    # is a bitcast, and the experts are the period's own as they always
    # were -- no group sizes at an offset into a stack
    assert cfg.num_periods == 1 and not stacked_group_sizes(text)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(nbytes(s) for s in cache.values())
    # no copy of the state tree (a slot a sequence and one x 3 layers x
    # 2 MiB) in the temporaries of a decode step; its attention layer's
    # q (with its gate), k and v are held to rows and none of their
    # weights is copied in front of its dot (``mixers.base.held``; left
    # free: ``bf16[8192,2048]`` and two ``bf16[512,2048]``). A chunk wider
    # than a quarter of the hidden size holds nothing
    held = low.as_text().count("@LayoutConstraint")
    if bucket[1] == 1:
        assert mem.temp_size_in_bytes < nbytes(cache["ssm"])
        assert held == 3 and staged_projections(text, params) == []
    else:
        assert held == 0
