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
weights where they lie (``paged_model._held``). Nothing runs. And the
other way round:
``mistral-7b``'s and ``qwen3-next-80b-a3b``'s ``[S, 1]`` and ``[1, C]``
programs hold the kernel names and operand counts they held before the
block-sparse variants were added to ``ops/paged_attention.py`` (read off
the lowered program: what the compiler is handed). See
tests/test_tpu_compile.py for the method."""

import json
import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from deepspeed_tpu.ops import latent_attention as la  # noqa: E402
from deepspeed_tpu.ops import paged_attention as pa  # noqa: E402
from deepspeed_tpu.ops import pallas_utils  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HBM = 15.75 * 2 ** 30


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2").devices
    except Exception as e:  # no libtpu / unknown topology on this host
        pytest.skip(f"cannot describe a v5e topology here: {e}")


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


@pytest.fixture(scope="module")
def cell_chunk(v5e, _no_persistent_cache):
    """The cell's widest chunk forward, ``[1, 2048]``, lowered and
    compiled once for the tests that read it: ``(lowered, compiled,
    params, cache, cfg)``."""
    with pytest.MonkeyPatch.context() as patch:
        lowered, params, cache, cfg = _lowered("minicpm-sala", v5e[0],
                                               (1, 2048), patch)
        return lowered, lowered.compile(), params, cache, cfg


def _nbytes(s):
    return math.prod(s.shape) * jnp.dtype(s.dtype).itemsize


def _lowered(name, device, bucket, monkeypatch, layers=None):
    """The configuration's paged forward at ``bucket``, lowered for the
    described device: ``(lowered, params, cache, cfg)``."""
    from deepspeed_tpu.inference.v2 import modules
    from deepspeed_tpu.inference.v2.engine_v2 import \
        RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.paged_model import (PagedCausalLM,
                                                        fuse_qkv)
    from deepspeed_tpu.models import hybrid
    from deepspeed_tpu.models import transformer as tr

    monkeypatch.setattr(pa, "_on_tpu", lambda: True)
    monkeypatch.setattr(la, "_on_tpu", lambda: True)
    monkeypatch.setattr(modules, "on_tpu", lambda: True)
    monkeypatch.setattr(pallas_utils, "on_tpu", lambda: True)
    with open(os.path.join(REPO, "benchmark", "configs",
                           name + ".json")) as f:
        body = json.load(f)
    arch = dict(body["transformer_config"], dtype=jnp.bfloat16)
    if layers:
        arch["num_layers"] = layers
    cfg = tr.TransformerConfig(**arch)
    sizing = RaggedInferenceEngineConfig(**{
        k: v for k, v in body["engine"].items() if not k.startswith("_")})
    model = tr.CausalLM(cfg)
    bs = sizing.kv_block_size
    MB = -(-cfg.max_seq_len // bs)
    paged = PagedCausalLM(model, bs, MB,
                          max_batch_tokens=sizing.max_ragged_batch_size)
    one = SingleDeviceSharding(device)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    params = jax.tree.map(
        lambda a: spec(a.shape, jnp.bfloat16),
        jax.eval_shape(lambda k: fuse_qkv(model.init(k)),
                       jax.random.PRNGKey(0)))
    groups = cfg.kv_groups()
    cache = {}
    for g, ((_, n), layout) in enumerate(zip(groups, cfg.kv_layouts(bs))):
        for leaf, block in layout.items():
            cache[leaf + (str(g) if g else "")] = spec(
                (n, sizing.kv_blocks) + block, jnp.bfloat16)
    N, C = bucket
    args = [params, cache, spec((N, C), jnp.int32), spec((N,), jnp.int32),
            spec((N,), jnp.int32),
            spec((N, MB) if len(groups) == 1 else (len(groups), N, MB),
                 jnp.int32)]
    if cfg.is_hybrid and cfg.num_linear_layers:
        slots = sizing.max_ragged_sequence_count + 1
        for leaf, (shape, dt) in hybrid.state_shapes(cfg, slots).items():
            cache[leaf] = spec(shape, dt)
        args.append(spec((N,), jnp.int32))
    return paged.forward.lower(*args), params, cache, cfg


def _kernels(text):
    return re.findall(r"%([a-z_\-]+)[.\d]* = [^\n]*tpu_custom_call", text)


def test_the_select_kernel_at_the_files_sizes(v5e, monkeypatch):
    """``paged_attention_select`` by itself at a decode step of 32 rows:
    a table of 128 blocks a K/V head in scalar memory, one K/V head a
    grid step. (The whole ``[32, 1]`` forward compiled for the described
    chip with 101 MiB of temporaries when this file was written, and runs
    on the chip in the cell: PERF.md section 4.)"""
    monkeypatch.setattr(pa, "_on_tpu", lambda: True)
    monkeypatch.setattr(pallas_utils, "on_tpu", lambda: True)
    one = SingleDeviceSharding(v5e[0])
    spec = lambda shape, dt: jax.ShapeDtypeStruct(      # noqa: E731
        shape, dt, sharding=one)
    pool = spec((2, 16384, 2, 64, 128), jnp.bfloat16)
    text = jax.jit(lambda q, k, v, t, n, p, layer: pa.paged_attention_select(
        q, k, v, t, n, p, layer=layer)).lower(
            spec((32, 1, 32, 128), jnp.bfloat16), pool, pool,
            spec((32, 2, 128), jnp.int32), spec((32,), jnp.int32),
            spec((32,), jnp.int32), spec((), jnp.int32)).compile().as_text()
    assert _kernels(text).count("paged_attention_select") == 1


@pytest.mark.parametrize("bucket", [(1, 2048)],
                         ids=lambda b: f"{b[0]}x{b[1]}")
def test_the_cells_forwards_at_the_files_sizes(cell_chunk, bucket):
    _, compiled, params, cache, cfg = cell_chunk
    assert cfg.kv_groups() == ((0, 2),)
    assert cfg.kv_layouts(64) == ({"k": (2, 64, 128), "v": (2, 64, 128),
                                   "kc": (4, 2, 128)},)
    assert cache["lightning"].shape == (6, 33, 32, 128, 128)
    text = compiled.as_text()
    kernels = _kernels(text)
    N, C = bucket
    # two sparse layers, one period each (the scan's body holds one): a
    # one-token row walks its selected table, a chunk every live block
    # under the mask, 128 positions a call
    if C == 1:
        assert kernels.count("paged_attention_select") == 1
        assert "paged_attention_mask" not in kernels
    else:
        assert kernels.count("paged_attention_mask") == max(1, C // 128)
        assert "paged_attention_select" not in kernels
    assert "paged_attention" not in kernels
    # the selection holds no sort
    assert not re.search(r" sort\(|topk|TopK|top-k", text)
    scoped = re.findall(
        r'%paged_attention_[a-z]+[.\d]* = [^\n]*op_name="([^"]*)"', text)
    assert scoped and all("sparse_attn/attend/" in s for s in scoped)
    for name in ("block_compress", "block_score", "block_select",
                 "lightning_attn/lightning_scan",
                 "lightning_attn/lightning_proj"):
        assert name + "/" in text, name
    mem = compiled.memory_analysis()
    pool = sum(_nbytes(s) for s in cache.values())
    weights = sum(_nbytes(s) for s in jax.tree.leaves(params))
    assert mem.alias_size_in_bytes >= pool
    assert weights + pool + mem.temp_size_in_bytes < HBM - 4 * 2 ** 30, (
        weights / 2 ** 30, pool / 2 ** 30, mem.temp_size_in_bytes / 2 ** 30)
    print(f"[{N}x{C}] weights {weights / 2**30:.2f} GiB pool+state "
          f"{pool / 2**30:.2f} GiB temporaries "
          f"{mem.temp_size_in_bytes / 2**20:.1f} MiB")


#: a buffer as large as a projection's slice of its stack that is a
#: copy's or a slicing fusion's result: a weight staged in front of its
#: matmul (asynchronous slices into fast memory are prefetches)
STAGED = (r"%((?:copy|[\w\-]*slice[\w\-]*fusion)[.\d]*) = "
          r"bf16\[(?:1,)?(?:4096,4096|4096,256|256,4096)\]")


def test_a_step_multiplies_by_its_weights_where_they_lie(
        v5e, cell_chunk, monkeypatch):
    """A two-row step holds the outputs that a consumer batches by head
    to rows (``paged_model._held``: q, k, v and the gate of a period's
    three lightning layers and of its block-sparse one), so no
    projection's weight is taken out of its stack and transposed in
    front of its matmul. The widest chunk holds the lightning layers'
    q, k and v alone, and there the compiler stages the sparse layer's
    four as it did — a slicing fusion and a copy each."""
    lowered, *_ = _lowered("minicpm-sala", v5e[0], (2, 1), monkeypatch)
    assert lowered.as_text().count("@LayoutConstraint") == 16
    assert not re.findall(STAGED, lowered.compile().as_text())
    wide, compiled, *_ = cell_chunk
    assert wide.as_text().count("@LayoutConstraint") == 9
    assert len(re.findall(STAGED, compiled.as_text())) == 2 * 4
    # all sixteen again up to a quarter of the hidden size in rows
    narrow, *_ = _lowered("minicpm-sala", v5e[0], (1, 1024), monkeypatch)
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
    slopes; q, k, v) — no table a head, no mask; and no output held to
    a layout (``paged_model._held``: their q goes to the kernel)."""
    lowered, *_ = _lowered(name, v5e[0], bucket, monkeypatch, layers)
    assert "@LayoutConstraint" not in lowered.as_text()
    calls = re.findall(r"stablehlo\.custom_call @tpu_custom_call\(([^)]*)\)"
                       r'[^\n]*?kernel_name = \\?"([a-z_]+)', lowered.as_text())
    assert [name for _, name in calls].count("paged_attention") == 1
    assert not {"paged_attention_select", "paged_attention_mask"} \
        & {name for _, name in calls}
    call = next(args for args, name in calls if name == "paged_attention")
    assert call.count("%") == operands, call
