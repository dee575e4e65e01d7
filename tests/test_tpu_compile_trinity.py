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
at experts 3,072 wide; every pool leaf aliased to the output. See
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

from deepspeed_tpu.ops import paged_attention as pa  # noqa: E402
from deepspeed_tpu.ops import pallas_utils  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: one chip's memory, and what the compiled temporaries may take of it
#: beside the resident weights and pools
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
                           "trinity-large-preview.json")) as f:
        return json.load(f)


def _nbytes(s):
    return math.prod(s.shape) * jnp.dtype(s.dtype).itemsize


@pytest.mark.parametrize("bucket", [(1, 64), (1, 128), (1, 256), (1, 512),
                                    (1, 1024), (1, 2048), (32, 1)],
                         ids=lambda b: f"{b[0]}x{b[1]}")
def test_the_cells_forwards_at_the_files_sizes(v5e, bucket, monkeypatch):
    from deepspeed_tpu.inference.v2 import modules
    from deepspeed_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2, RaggedInferenceEngineConfig)
    from deepspeed_tpu.inference.v2.paged_model import PagedCausalLM
    from deepspeed_tpu.models import transformer as tr

    monkeypatch.setattr(pa, "_on_tpu", lambda: True)
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
    # the pools as the engine would size them (its rule, not a copy of it)
    sized = InferenceEngineV2.window_pool_blocks
    shell = type("E", (), {"config": sizing})()
    groups = cfg.kv_groups()
    assert groups == ((0, 1), (4096, 4))
    cache = {}
    for g, (window, layers) in enumerate(groups):
        for name in ("k", "v"):
            cache[name + (str(g) if g else "")] = spec(
                (layers, sized(shell, window), cfg.kv_heads, bs,
                 cfg.head_dim), jnp.bfloat16)
    N, C = bucket
    compiled = paged.forward.lower(
        params, cache, spec((N, C), jnp.int32), spec((N,), jnp.int32),
        spec((N,), jnp.int32), spec((len(groups), N, MB), jnp.int32)
    ).compile()
    text = compiled.as_text()
    kernels = re.findall(r"%([a-z_\-]+)[.\d]* = [^\n]*tpu_custom_call", text)
    # five attention layers; a chunk over MAX_QUERY_ROWS // 6 tokens is cut
    # in pieces that divide it
    tile = pa._chunk_tile(C, cfg.num_heads // cfg.kv_heads)
    assert kernels.count("paged_attention") == 5 * (C // tile)
    if C >= 1024:
        assert tile == 256
    # gate, up, down in each of the four sparse layers, nothing of XLA's own
    assert kernels.count("gmm") == 12
    assert not any(k.startswith("ragged") for k in kernels)
    scoped = re.findall(r'%paged_attention[.\d]* = [^\n]*op_name="([^"]*)"',
                        text)
    assert scoped and all("/attend/" in s and ("window_attn" in s
                                               or "full_attn" in s)
                          for s in scoped)
    assert sum("full_attn" in s for s in scoped) == C // tile

    mem = compiled.memory_analysis()
    pools = sum(_nbytes(s) for s in cache.values())
    weights = sum(_nbytes(s) for s in jax.tree.leaves(params))
    assert mem.alias_size_in_bytes >= pools
    # weights + pools + this forward's temporaries fit the chip, with
    # room for the check's float32 reference (~2.5 GiB) when nothing runs
    assert weights + pools + mem.temp_size_in_bytes < HBM - 2 * 2 ** 30, (
        weights / 2 ** 30, pools / 2 ** 30, mem.temp_size_in_bytes / 2 ** 30)
    print(f"[{N}x{C}] weights {weights / 2**30:.2f} GiB pools "
          f"{pools / 2**30:.2f} GiB temporaries "
          f"{mem.temp_size_in_bytes / 2**20:.1f} MiB")
