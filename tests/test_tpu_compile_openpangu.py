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
output. See tests/test_tpu_compile.py for the method."""

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
#: one chip's memory, and what the compiled temporaries may take of it
#: beside the resident weights and pool
HBM = 15.75 * 2 ** 30


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2").devices
    except Exception as e:  # no libtpu / unknown topology on this host
        pytest.skip(f"cannot describe a v5e topology here: {e}")


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


def _file():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "openpangu-ultra-moe-718b.json")) as f:
        return json.load(f)


def _nbytes(s):
    return math.prod(s.shape) * jnp.dtype(s.dtype).itemsize


@pytest.mark.parametrize("bucket", [(1, 64), (1, 128), (1, 256), (1, 512),
                                    (1, 1024), (1, 2048), (32, 1)],
                         ids=lambda b: f"{b[0]}x{b[1]}")
def test_the_cells_forwards_at_the_files_sizes(v5e, bucket, monkeypatch):
    from deepspeed_tpu.inference.v2 import modules
    from deepspeed_tpu.inference.v2.engine_v2 import \
        RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2.paged_model import PagedCausalLM
    from deepspeed_tpu.models import transformer as tr

    monkeypatch.setattr(pa, "_on_tpu", lambda: True)
    monkeypatch.setattr(la, "_on_tpu", lambda: True)
    monkeypatch.setattr(modules, "on_tpu", lambda: True)
    monkeypatch.setattr(pallas_utils, "on_tpu", lambda: True)
    body = _file()
    cfg = tr.TransformerConfig(**dict(body["transformer_config"],
                                      dtype=jnp.bfloat16))
    sizing = RaggedInferenceEngineConfig(**{
        k: v for k, v in body["engine"].items() if not k.startswith("_")})
    assert (bucket[1] <= sizing.max_chunk_tokens
            and bucket[0] <= sizing.max_ragged_sequence_count)
    model = tr.CausalLM(cfg)
    bs = sizing.kv_block_size
    MB = -(-cfg.max_seq_len // bs)
    paged = PagedCausalLM(model, bs, MB,
                          max_batch_tokens=sizing.max_ragged_batch_size)
    one = SingleDeviceSharding(v5e[0])

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    params = jax.tree.map(
        lambda a: spec(a.shape, jnp.bfloat16),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    # the pool in the layout the model states: one leaf, no head axis
    assert cfg.kv_groups() == ((0, 5),)
    leaves, block_shape = cfg.kv_layout(bs)
    assert leaves == ("kv",) and block_shape == (64, 640)
    cache = {"kv": spec((5, sizing.kv_blocks) + block_shape, jnp.bfloat16)}
    N, C = bucket
    compiled = paged.forward.lower(
        params, cache, spec((N, C), jnp.int32), spec((N,), jnp.int32),
        spec((N,), jnp.int32), spec((N, MB), jnp.int32)).compile()
    text = compiled.as_text()
    kernels = re.findall(r"%([a-z_\-]+)[.\d]* = [^\n]*tpu_custom_call", text)
    # five latent layers (the leading one and the period of four), one
    # kernel call each: absorbed up to the switch, expanded past it (the
    # call sits inside the tiles' loop)
    absorbed = C <= la.ABSORB_MAX_QUERIES
    assert kernels.count("mla_decode") == (5 if absorbed else 0)
    assert kernels.count("mla_prefill") == (0 if absorbed else 5)
    assert "paged_attention" not in kernels
    # gate, up, down in each of the four sparse layers, nothing of XLA's own
    assert kernels.count("gmm") == 12
    assert not any(k.startswith("ragged") for k in kernels)
    scoped = re.findall(r'%mla_[a-z]+[.\d]* = [^\n]*op_name="([^"]*)"', text)
    assert scoped and all("latent_attn" in s and "/attend/" in s
                          for s in scoped)
    assert ("kv_expand" in text) == (not absorbed)

    mem = compiled.memory_analysis()
    pool = sum(_nbytes(s) for s in cache.values())
    weights = sum(_nbytes(s) for s in jax.tree.leaves(params))
    assert mem.alias_size_in_bytes >= pool
    # weights + pool + this forward's temporaries fit the chip
    assert weights + pool + mem.temp_size_in_bytes < HBM - 2 ** 30, (
        weights / 2 ** 30, pool / 2 ** 30, mem.temp_size_in_bytes / 2 ** 30)
    print(f"[{N}x{C}] weights {weights / 2**30:.2f} GiB pool "
          f"{pool / 2**30:.2f} GiB temporaries "
          f"{mem.temp_size_in_bytes / 2**20:.1f} MiB")
