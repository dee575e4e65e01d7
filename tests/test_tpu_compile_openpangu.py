"""The cell ``openpangu-ultra-moe-718b.longprompt``'s forwards compiled
for a *described* TPU v5e at the sizes its configuration's file states —
the weights of one leading dense layer and four sparse layers at the
published widths, the latent pool at the file's size, the table of 33,792
positions — for every chunk bucket ``[1, C]`` and the widest decode step
``[S, 1]``: what the chip's compiler refuses, and what does not fit
beside the weights, shows here and not on the chip. Nothing runs. The
absorbed kernel (``mla_decode``) at 128 query heads x 640 lanes against
512 keys a turn in VMEM, one call a layer, for ``[S, 1]`` and the chunks
up to ``ABSORB_MAX_QUERIES``; the expanded kernel (``mla_prefill``) with
``kv_expand``'s temporaries — a tile's K/V heads, the carry — for the
wider chunks, whatever the context (the loop's trip count is dynamic, so
a 32k context compiles to the same program); the grouped matmul as the
Pallas ``gmm`` at experts 2,048 wide; the one pool leaf aliased to the
output. One interior bucket of the expanded side (``1x1024``: ``1x512``
and ``1x2048`` hold the same kernels, counts and side of the crossing)
compiles under ``-m slow``; which side every bucket takes is held
without compiling. See tests/test_tpu_compile.py
for the method and tests/tpu_compile_harness.py for what is shared."""

import re

import pytest
from tpu_compile_harness import (_no_persistent_cache, bucket_id,  # noqa: F401
                                 configuration, fits_beside, kernels, lowered,
                                 v5e)

from deepspeed_tpu.ops import latent_attention as la

NAME = "openpangu-ultra-moe-718b"
BUCKETS = [(1, 64), (1, 128), (1, 256), (1, 512),
           pytest.param((1, 1024), marks=pytest.mark.slow), (1, 2048), (32, 1)]


def _absorbed(C):
    """Whether a row of ``C`` positions runs the absorbed kernel."""
    return C <= la.ABSORB_MAX_QUERIES


@pytest.mark.parametrize("bucket,kernel", [
    ((1, 64), "mla_decode"), ((1, 128), "mla_decode"),
    ((1, 256), "mla_prefill"), ((1, 512), "mla_prefill"),
    ((1, 1024), "mla_prefill"), ((1, 2048), "mla_prefill"),
    ((32, 1), "mla_decode")], ids=bucket_id)
def test_the_kernel_each_bucket_takes(bucket, kernel):
    """Without compiling: the side of ``ABSORB_MAX_QUERIES`` each of the
    file's buckets lies on, by name -- so a crossing that moves shows in
    tier-1 at the bucket whose compile is ``slow`` too."""
    cfg, sizes = configuration(NAME)
    assert cfg.kv_groups() == ((0, 5),)
    assert bucket[1] <= sizes["max_chunk_tokens"]
    assert ("mla_decode" if _absorbed(bucket[1]) else "mla_prefill") == kernel


@pytest.mark.parametrize("bucket", BUCKETS, ids=bucket_id)
def test_the_cells_forwards_at_the_files_sizes(v5e, bucket, monkeypatch):
    low, params, cache, cfg = lowered(NAME, v5e[0], bucket, monkeypatch)
    # the pool in the layout the model states: one leaf, no head axis
    assert cfg.kv_groups() == ((0, 5),)
    assert cfg.kv_layout(64) == (("kv",), (64, 640))
    assert cache["kv"].shape == (5, 7680, 64, 640)
    compiled = low.compile()
    text = compiled.as_text()
    found = kernels(text)
    # five latent layers (the leading one and the period of four), one
    # kernel call each: absorbed up to the switch, expanded past it (the
    # call sits inside the tiles' loop)
    absorbed = _absorbed(bucket[1])
    assert found.count("mla_decode") == (5 if absorbed else 0)
    assert found.count("mla_prefill") == (0 if absorbed else 5)
    assert "paged_attention" not in found
    # gate, up, down in each of the four sparse layers, nothing of XLA's own
    assert found.count("gmm") == 12
    assert not any(k.startswith("ragged") for k in found)
    scoped = re.findall(r'%mla_[a-z]+[.\d]* = [^\n]*op_name="([^"]*)"', text)
    assert scoped and all("latent_attn" in s and "/attend/" in s
                          for s in scoped)
    assert ("kv_expand" in text) == (not absorbed)
    # weights + pool + this forward's temporaries fit the chip
    fits_beside(compiled, params, cache, bucket, headroom=2 ** 30)
