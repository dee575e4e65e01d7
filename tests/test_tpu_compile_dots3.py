"""The cell ``dots3-note-prev.longctx``'s forwards compiled for a
*described* TPU v5e at the sizes its configuration's file states — one
leading dense layer and a period of three window layers and one
whole-context layer at the published widths, both pools at the file's
sizes, the table of 66,560 positions — at a chunk between the two kinds'
crossings (the sparse layers expanded under the mask, the window layers
absorbed) and at the widest chunk (both expanded; the narrow chunks and
the decode steps run the absorbed kernels of the first, a row a position):
what the chip's compiler refuses, and what does not fit beside the
weights, shows here and not on the chip. Nothing runs. The sparse layers'
``index_score`` kernel and either ``mla_sparse_decode`` over gathered
rows or ``mla_sparse_prefill`` under the selection's mask; the window
layers' ``mla_window_decode`` at 64 heads x 1,152 lanes or
``mla_window_prefill`` with the rope joined to a 192-wide nope; three
leaves, each aliased to the output. See tests/test_tpu_compile.py for
the method and tests/tpu_compile_harness.py for what is shared; which
kernels every bucket takes is also held without compiling."""

import re

import pytest
from tpu_compile_harness import (_no_persistent_cache, bucket_id,  # noqa: F401
                                 configuration, fits_beside, kernels, lowered,
                                 v5e)

from deepspeed_tpu.models import hybrid
from deepspeed_tpu.ops import latent_attention as la

NAME = "dots3-note-prev"
BUCKETS = [(2, 1), (1, 128), (1, 256), (1, 2048)]


def _absorbed(cfg, C):
    """Whether a row of ``C`` positions runs absorbed: in the sparse
    layers, in the window layers."""
    return (C <= la.ABSORB_MAX_QUERIES,
            C <= hybrid.absorb_limit(cfg, "latent_window"))


@pytest.mark.parametrize("bucket,sparse,window", [
    ((2, 1), "mla_sparse_decode", "mla_window_decode"),
    ((1, 128), "mla_sparse_decode", "mla_window_decode"),
    ((1, 256), "mla_sparse_prefill", "mla_window_decode"),
    ((1, 2048), "mla_sparse_prefill", "mla_window_prefill")], ids=bucket_id)
def test_the_kernels_each_bucket_takes(bucket, sparse, window):
    """Without compiling: the side of each kind's crossing every one of
    the file's buckets lies on, by name (the four differ in kind: all
    four compile in tier-1)."""
    cfg, sizes = configuration(NAME)
    assert bucket[1] <= sizes["max_chunk_tokens"]
    took = _absorbed(cfg, bucket[1])
    assert ("mla_sparse_decode" if took[0] else "mla_sparse_prefill",
            "mla_window_decode" if took[1] else "mla_window_prefill") == (
        sparse, window)


@pytest.mark.parametrize("bucket", BUCKETS, ids=bucket_id)
def test_the_cells_forwards_at_the_files_sizes(v5e, bucket, monkeypatch):
    from deepspeed_tpu.inference.v2 import paged_model

    low, params, cache, cfg = lowered(NAME, v5e[0], bucket, monkeypatch)
    # the pools in the layouts the model states: two groups, three leaves
    assert cfg.kv_groups() == ((0, 2), (513, 3))
    assert cfg.kv_layouts(64) == ({"kv": (64, 640), "ki": (64, 128)},
                                  {"kv": (64, 1152)})
    window_blocks = 32 * (-(-513 // 64) + 2) + 4096 // 64
    assert {n: s.shape for n, s in cache.items()} == {
        "kv": (2, 16384, 64, 640), "ki": (2, 16384, 64, 128),
        "kv1": (3, window_blocks, 64, 1152)}
    compiled = low.compile()
    text = compiled.as_text()
    found = kernels(text)
    # two whole-context layers (the leading one and the period's), three
    # window layers: one attention kernel call each, by the kind's path
    sparse_absorbed, window_absorbed = _absorbed(cfg, bucket[1])
    assert hybrid.absorb_limit(cfg, "latent_window") == 256
    # (the scores are taken at one of two widths: a branch each)
    assert found.count("index_score") == 2 * (len(paged_model.SELECT_WIDTHS)
                                              + 1)
    assert found.count("mla_sparse_decode") == (2 if sparse_absorbed else 0)
    assert found.count("mla_sparse_prefill") == (0 if sparse_absorbed else 2)
    assert found.count("mla_window_decode") == (3 if window_absorbed else 0)
    assert found.count("mla_window_prefill") == (0 if window_absorbed else 3)
    assert not {"mla_decode", "mla_prefill", "paged_attention"} & set(found)
    assert found.count("gmm") == 12
    # the selection holds no sort (one-token rows and narrow chunks count
    # the threshold out and compact, as the wide chunks' mask does): what
    # sorts is the experts' routing, over 256 experts and a row's pairs
    sorts = re.findall(r' sort\([^\n]*op_name="([^"]*)"', text)
    assert sorts and all("/mlp/experts/" in s for s in sorts), sorts
    assert not re.search(r"topk|TopK|top-k", text)
    scoped = re.findall(r'%index_score[.\d]* = [^\n]*op_name="([^"]*)"', text)
    assert scoped and all("latent_attn/index/" in s and "/index_score/" in s
                          for s in scoped)
    scoped = re.findall(r'%mla_window[a-z_]+[.\d]* = [^\n]*op_name="([^"]*)"',
                        text)
    assert scoped and all("window_latent_attn" in s and "/attend/" in s
                          for s in scoped)
    fits_beside(compiled, params, cache, bucket, headroom=2 ** 30)
