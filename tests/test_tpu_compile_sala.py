"""The cell ``minicpm-sala.deepctx``'s forwards compiled for a
*described* TPU v5e at the sizes its configuration's file states — two
periods of a sparse layer and three lightning layers at the published
widths, the pool's three leaves and the lightning state at the file's
sizes, the table of 99,328 positions — at the widest chunk, and the
one-token rows' kernel alone at a decode step of every row: what the
chip's compiler refuses
(the select kernel's table a K/V head, the mask kernel's spread of a
block's bit over its keys), and what does not fit beside the weights,
shows here and not on the chip; and that a step multiplies by its
weights where they lie (``mixers.base.held``). Nothing runs. And the
other way round:
``mistral-7b``'s and ``qwen3-next-80b-a3b``'s ``[S, 1]`` and ``[1, C]``
programs hold the kernel names and operand counts they held before the
block-sparse variants were added to ``ops/paged_attention.py`` (read off
the lowered program: what the compiler is handed). See
tests/test_tpu_compile.py for the method and tests/tpu_compile_harness.py
for what is shared."""

import re

import jax
import jax.numpy as jnp
import pytest
from tpu_compile_harness import (_no_persistent_cache, bucket_id,  # noqa: F401
                                 configuration, fits_beside, kernels, lowered,
                                 spec_on, v5e)

from deepspeed_tpu.ops import paged_attention as pa
from deepspeed_tpu.ops import pallas_utils

NAME = "minicpm-sala"


@pytest.fixture(scope="module")
def cell_chunk(v5e, _no_persistent_cache):
    """The cell's widest chunk forward, ``[1, 2048]``, lowered and
    compiled once for the tests that read it: ``(lowered, compiled,
    params, cache, cfg)``."""
    with pytest.MonkeyPatch.context() as patch:
        low, params, cache, cfg = lowered(NAME, v5e[0], (1, 2048), patch)
        return low, low.compile(), params, cache, cfg


def _sparse_calls(cfg, C):
    """The block-sparse layers' kernel at a row of ``C`` positions, and
    its calls a layer: a piece of ``_chunk_tile`` each."""
    if C == 1:
        return "paged_attention_select", 1
    return "paged_attention_mask", C // pa._chunk_tile(
        C, cfg.num_heads // cfg.kv_heads)


@pytest.mark.parametrize("bucket,kernel,calls", [
    ((1, 2048), "paged_attention_mask", 16),
    ((1, 1024), "paged_attention_mask", 8),
    ((2, 1), "paged_attention_select", 1)], ids=bucket_id)
def test_the_kernel_each_bucket_takes(bucket, kernel, calls):
    """Without compiling: a one-token row walks its selected table, a
    chunk every live block under the mask, 128 positions a call (16
    query heads a KV head) -- for the buckets this file lowers."""
    cfg, sizes = configuration(NAME)
    assert bucket[1] <= sizes["max_chunk_tokens"]
    assert cfg.num_heads // cfg.kv_heads == 16
    assert _sparse_calls(cfg, bucket[1]) == (kernel, calls)


def test_the_select_kernel_at_the_files_sizes(v5e, monkeypatch):
    """``paged_attention_select`` by itself at a decode step of 32 rows:
    a table of 128 blocks a K/V head in scalar memory, one K/V head a
    grid step. (The whole ``[32, 1]`` forward compiled for the described
    chip with 101 MiB of temporaries when this file was written, and runs
    on the chip in the cell: PERF.md section 4.)"""
    monkeypatch.setattr(pa, "_on_tpu", lambda: True)
    monkeypatch.setattr(pallas_utils, "on_tpu", lambda: True)
    spec = spec_on(v5e[0])
    pool = spec((2, 16384, 2, 64, 128), jnp.bfloat16)
    text = jax.jit(lambda q, k, v, t, n, p, layer: pa.paged_attention_select(
        q, k, v, t, n, p, layer=layer)).lower(
            spec((32, 1, 32, 128), jnp.bfloat16), pool, pool,
            spec((32, 2, 128), jnp.int32), spec((32,), jnp.int32),
            spec((32,), jnp.int32), spec((), jnp.int32)).compile().as_text()
    assert kernels(text).count("paged_attention_select") == 1


@pytest.mark.parametrize("bucket", [(1, 2048)], ids=bucket_id)
def test_the_cells_forwards_at_the_files_sizes(cell_chunk, bucket):
    _, compiled, params, cache, cfg = cell_chunk
    assert cfg.kv_groups() == ((0, 2),)
    assert cfg.kv_layouts(64) == ({"k": (2, 64, 128), "v": (2, 64, 128),
                                   "kc": (4, 2, 128)},)
    assert cache["lightning"].shape == (6, 33, 32, 128, 128)
    text = compiled.as_text()
    found = kernels(text)
    # two sparse layers, one period each (the scan's body holds one): a
    # one-token row walks its selected table, a chunk every live block
    # under the mask, 128 positions a call
    kernel, calls = _sparse_calls(cfg, bucket[1])
    assert found.count(kernel) == calls
    assert not {"paged_attention_select", "paged_attention_mask",
                "paged_attention"} - {kernel} & set(found)
    # the selection holds no sort
    assert not re.search(r" sort\(|topk|TopK|top-k", text)
    scoped = re.findall(
        r'%paged_attention_[a-z]+[.\d]* = [^\n]*op_name="([^"]*)"', text)
    assert scoped and all("sparse_attn/attend/" in s for s in scoped)
    for name in ("block_compress", "block_score", "block_select",
                 "lightning_attn/lightning_scan",
                 "lightning_attn/lightning_proj"):
        assert name + "/" in text, name
    fits_beside(compiled, params, cache, bucket, headroom=4 * 2 ** 30)


#: a buffer as large as a projection's slice of its stack that is a
#: copy's or a slicing fusion's result: a weight staged in front of its
#: matmul (asynchronous slices into fast memory are prefetches)
STAGED = (r"%((?:copy|[\w\-]*slice[\w\-]*fusion)[.\d]*) = "
          r"bf16\[(?:1,)?(?:4096,4096|4096,256|256,4096)\]")


def test_a_step_multiplies_by_its_weights_where_they_lie(
        v5e, cell_chunk, monkeypatch):
    """A two-row step holds the outputs that a consumer batches by head
    to rows (``mixers.base.held``: q, k, v and the gate of a period's
    three lightning layers and of its block-sparse one), so no
    projection's weight is taken out of its stack and transposed in
    front of its matmul. The widest chunk holds the lightning layers'
    q, k and v alone, and there the compiler stages the sparse layer's
    four as it did — a slicing fusion and a copy each."""
    step, *_ = lowered(NAME, v5e[0], (2, 1), monkeypatch)
    assert step.as_text().count("@LayoutConstraint") == 16
    assert not re.findall(STAGED, step.compile().as_text())
    wide, compiled, *_ = cell_chunk
    assert wide.as_text().count("@LayoutConstraint") == 9
    assert len(re.findall(STAGED, compiled.as_text())) == 2 * 4
    # all sixteen again up to a quarter of the hidden size in rows
    narrow, *_ = lowered(NAME, v5e[0], (1, 1024), monkeypatch)
    assert narrow.as_text().count("@LayoutConstraint") == 16


@pytest.mark.parametrize("name,bucket,layers,operands", [
    ("mistral-7b", (16, 1), 2, 8), ("mistral-7b", (1, 256), 2, 8),
    ("qwen3-next-80b-a3b", (16, 1), None, 8),
    ("qwen3-next-80b-a3b", (1, 256), None, 8)],
    ids=lambda v: str(v).replace(" ", ""))
def test_models_without_block_sparse_layers_lower_as_they_did(
        v5e, name, bucket, layers, operands, monkeypatch):
    """The paged kernel's call as it was before this file's cell: its
    name, one call a program (the scan's body holds the layer), eight
    operands (five scalar-prefetched: layer, tables, start, lengths,
    slopes; q, k, v) — no table a head, no mask. The dense forward holds
    no output to a layout (its one ``wqkv`` leaf, PR 37); a hybrid
    block's attention layer holds its q, k and v in these buckets, narrow
    both (``mixers.base.held``, PR 61)."""
    text = lowered(name, v5e[0], bucket, monkeypatch, layers)[0].as_text()
    assert text.count("@LayoutConstraint") == (
        3 if name.startswith("qwen3") else 0)
    calls = re.findall(r"stablehlo\.custom_call @tpu_custom_call\(([^)]*)\)"
                       r'[^\n]*?kernel_name = \\?"([a-z_]+)', text)
    assert [name for _, name in calls].count("paged_attention") == 1
    assert not {"paged_attention_select", "paged_attention_mask"} \
        & {name for _, name in calls}
    call = next(args for args, name in calls if name == "paged_attention")
    assert call.count("%") == operands, call
