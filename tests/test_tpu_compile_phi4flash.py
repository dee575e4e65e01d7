"""The cell ``phi-4-mini-flash-reasoning.deepthink``'s forwards compiled
for a *described* TPU v5e at the sizes its configuration's file states —
all 32 layers at the published widths, the whole 200,064-row tied
embedding, both layer groups' pools at the file's sizes, 33 state slots,
the table of 36,864 positions — for the decode step ``[32, 1]`` and the
widest chunk ``[1, 2048]``: what the chip's compiler refuses, and what
does not fit beside the weights, shows here and not on the chip. Nothing
runs. Differential attention on the paged kernel that is there, at 128
wide over 10 joined K/V pairs (4 query rows a pair); the cross layers'
calls read the whole-context group's one layer; in the chunk forward
every call behind the exit is one position a row. See
tests/test_tpu_compile.py for the method and tests/tpu_compile_harness.py
for what is shared.

The cell ``ai21-jamba2-3b.chatrate``'s forwards join them as cases of
their own (the S6 layer at the same widths, with its norms inside): all
28 layers, the 65,536-row tied embedding, the pool of 36,864 blocks, 129
state slots, the table of 18,432 positions — the one-token step over all
128 seats, ``[128, 1]``, and the chunk ``[1, 512]`` (the mix's mean
prompt is 399 tokens: the cell's commonest chunk program, and a quarter of
the widest one's kernel call sites to compile); the paged kernel walks a
query group of 20 heads over the ONE K/V head."""

import re

import pytest
from tpu_compile_harness import (_no_persistent_cache, bucket_id,  # noqa: F401
                                 configuration, fits_beside, kernels, lowered,
                                 staged_projections, v5e)

from deepspeed_tpu.ops import paged_attention as pa

NAME = "phi-4-mini-flash-reasoning"
BUCKETS = [(32, 1), (1, 2048)]
JAMBA = "ai21-jamba2-3b"
JAMBA_BUCKETS = [(128, 1), (1, 512)]


@pytest.mark.parametrize("bucket", BUCKETS, ids=bucket_id)
def test_the_cells_forwards_at_the_files_sizes(v5e, bucket, monkeypatch):
    low, params, cache, cfg = lowered(NAME, v5e[0], bucket, monkeypatch)
    # pools by what is written: one whole-context layer, eight window
    # layers, K/V heads joined in pairs; nine S6 layers' state
    assert cfg.kv_groups() == ((0, 1), (512, 8))
    assert cache["k"].shape == cache["v"].shape == (1, 5120, 10, 64, 128)
    assert cache["k1"].shape == cache["v1"].shape == (8, 384, 10, 64, 128)
    assert cache["mamba1_ssm"].shape == (9, 33, 16, 5120)
    assert cache["mamba1_conv"].shape == (9, 33, 3, 5120)
    compiled = low.compile()
    text = compiled.as_text()
    found = kernels(text)
    # one call site in each scan's body and in each inline layer, whatever
    # the depth: the window layers' (a scan of eight; a chunk over
    # MAX_QUERY_ROWS // 4 tokens cut in pieces), layer 17's and the cross
    # layers' (a scan of seven) -- both one position a row in a chunk
    # forward too, behind the exit; no other kernel
    pieces = bucket[1] // pa._chunk_tile(bucket[1], 4)
    assert found.count("paged_attention") == pieces + 2, found
    # a one-token forward steps the S6 state where it lies in its slots:
    # ``s6_step`` in the window run's scanned body and in layer 16; a
    # chunk forward holds none
    stepped = 2 if bucket[1] == 1 else 0
    assert found.count("s6_step") == stepped, found
    assert set(found) == {"paged_attention"} | (
        {"s6_step"} if stepped else set()), found
    scoped = re.findall(r'%paged_attention[.\d]* = [^\n]*op_name="([^"]*)"',
                        text)
    assert all("/attend/" in s for s in scoped)
    kinds = [s.split("/attend/")[0].rsplit("/", 1)[-1] for s in scoped]
    assert sorted(kinds) == ["cross_attn", "full_attn"] \
        + ["window_attn"] * pieces
    assert ["/xdec/" in s for s in scoped] \
        == [kind == "cross_attn" for kind in kinds]
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("mamba/mamba_scan", "mamba/mamba_state_io", "xdec/",
                  "gmu/", "mlp/dense_mlp", "logits"):
        assert any(scope in n for n in names), scope
    # weights + pools + state + this forward's temporaries fit the chip
    # with room for the check's float32 reference when nothing runs
    # (3.05 GiB of logits at 4,096 positions and about 1 GiB they are
    # made from)
    fits_beside(compiled, params, cache, bucket, headroom=4 * 2 ** 30)
    # held to rows (``mixers.base.held``): a step's q, k and v of the
    # window run's layer and of layer 17 and the cross run's q, and no
    # projection's weight is copied, transposed, in front of its dot (left
    # free: three ``bf16[1,2560,2560]``, four ``bf16[1,2560,1280]``). In
    # a chunk only what lies behind the exit is narrow, one position a
    # row -- layer 17's q and the cross run's; the 2,048 rows in front
    # stage the window run's q, k and v (a slice and a copy each) and
    # layer 17's k and v as they did
    staged = staged_projections(text, params)
    if bucket[1] == 1:
        assert low.as_text().count("@LayoutConstraint") == 7
        assert staged == []
    else:
        assert low.as_text().count("@LayoutConstraint") == 2
        assert sorted(dims for _, dims, _ in staged) == \
            ["1,2560,1280"] * 6 + ["1,2560,2560"] * 2
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < (64 if bucket[1] == 1 else 768) * 2 ** 20, temp / 2 ** 20


@pytest.mark.parametrize("bucket", JAMBA_BUCKETS,
                         ids=lambda b: "jamba-" + bucket_id(b))
def test_jambas_forwards_at_the_files_sizes(v5e, bucket, monkeypatch):
    low, params, cache, cfg = lowered(JAMBA, v5e[0], bucket, monkeypatch)
    # one layer group of two layers over one K/V head; 26 S6 layers' state
    # a slot a seat and the scratch one
    assert cfg.kv_groups() == ((0, 2),)
    assert cache["k"].shape == cache["v"].shape == (2, 36864, 1, 64, 128)
    assert cache["mamba1_ssm"].shape == (26, 129, 16, 5120)
    assert cache["mamba1_conv"].shape == (26, 129, 3, 5120)
    compiled = low.compile()
    text = compiled.as_text()
    found = kernels(text)
    # a call site in each of the two inline attention layers; a chunk over
    # MAX_QUERY_ROWS // 20 tokens is cut in pieces (the group of 20 heads
    # is rows of one K/V head's query block); no other kernel
    pieces = bucket[1] // pa._chunk_tile(bucket[1], 20)
    assert found.count("paged_attention") == 2 * pieces, found
    # a one-token forward steps the S6 state where it lies in its slots:
    # ``s6_step`` once in each of the three runs of S6 layers (a scanned
    # body each); a chunk forward holds none
    stepped = 3 if bucket[1] == 1 else 0
    assert found.count("s6_step") == stepped, found
    assert set(found) == {"paged_attention"} | (
        {"s6_step"} if stepped else set()), found
    if stepped:
        under = re.findall(r'%s6_step[.\d]* = [^\n]*op_name="([^"]*)"', text)
        assert under and all("/mamba/mamba_scan/" in s for s in under), under
    scoped = re.findall(r'%paged_attention[.\d]* = [^\n]*op_name="([^"]*)"',
                        text)
    assert scoped and all("/full_attn/attend/" in s for s in scoped)
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("mamba/mamba_norm", "mamba/mamba_scan",
                  "mamba/mamba_state_io", "mamba/mamba_proj",
                  "mlp/dense_mlp", "logits"):
        assert any(scope in n for n in names), scope
    # weights 5.64 GiB + pool 2.25 + state 1.10 + this forward's
    # temporaries fit the chip with room for the check's float32 reference
    # when nothing runs (1.1 GiB of logits and K/V rows at 4,096 positions
    # and about 1 GiB they are made from)
    fits_beside(compiled, params, cache, bucket, headroom=3 * 2 ** 30)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < (192 if bucket[1] == 1 else 384) * 2 ** 20, temp / 2 ** 20
