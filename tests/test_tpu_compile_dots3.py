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
the method."""

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


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


def _nbytes(s):
    return math.prod(s.shape) * jnp.dtype(s.dtype).itemsize


@pytest.mark.parametrize("bucket", [(2, 1), (1, 128), (1, 256), (1, 2048)],
                         ids=lambda b: f"{b[0]}x{b[1]}")
def test_the_cells_forwards_at_the_files_sizes(v5e, bucket, monkeypatch):
    from deepspeed_tpu.inference.v2 import modules
    from deepspeed_tpu.inference.v2.engine_v2 import \
        RaggedInferenceEngineConfig
    from deepspeed_tpu.inference.v2 import paged_model
    from deepspeed_tpu.inference.v2.paged_model import PagedCausalLM
    from deepspeed_tpu.models import hybrid
    from deepspeed_tpu.models import transformer as tr

    monkeypatch.setattr(pa, "_on_tpu", lambda: True)
    monkeypatch.setattr(la, "_on_tpu", lambda: True)
    monkeypatch.setattr(modules, "on_tpu", lambda: True)
    monkeypatch.setattr(pallas_utils, "on_tpu", lambda: True)
    with open(os.path.join(REPO, "benchmark", "configs",
                           "dots3-note-prev.json")) as f:
        body = json.load(f)
    cfg = tr.TransformerConfig(**dict(body["transformer_config"],
                                      dtype=jnp.bfloat16))
    sizing = RaggedInferenceEngineConfig(**{
        k: v for k, v in body["engine"].items() if not k.startswith("_")})
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
    # the pools in the layouts the model states: two groups, three leaves
    assert cfg.kv_groups() == ((0, 2), (513, 3))
    assert cfg.kv_layouts(bs) == ({"kv": (64, 640), "ki": (64, 128)},
                                  {"kv": (64, 1152)})
    window_blocks = 32 * (-(-513 // bs) + 2) + 4096 // bs
    cache = {"kv": spec((2, sizing.kv_blocks, 64, 640), jnp.bfloat16),
             "ki": spec((2, sizing.kv_blocks, 64, 128), jnp.bfloat16),
             "kv1": spec((3, window_blocks, 64, 1152), jnp.bfloat16)}
    N, C = bucket
    compiled = paged.forward.lower(
        params, cache, spec((N, C), jnp.int32), spec((N,), jnp.int32),
        spec((N,), jnp.int32), spec((2, N, MB), jnp.int32)).compile()
    text = compiled.as_text()
    kernels = re.findall(r"%([a-z_\-]+)[.\d]* = [^\n]*tpu_custom_call", text)
    # two whole-context layers (the leading one and the period's), three
    # window layers: one attention kernel call each, by the kind's path
    sparse_absorbed = C <= la.ABSORB_MAX_QUERIES
    window_absorbed = C <= hybrid.absorb_limit(cfg, "latent_window")
    assert hybrid.absorb_limit(cfg, "latent_window") == 256
    # (the scores are taken at one of two widths: a branch each)
    assert kernels.count("index_score") == 2 * (len(paged_model.SELECT_WIDTHS)
                                                + 1)
    assert kernels.count("mla_sparse_decode") == (2 if sparse_absorbed else 0)
    assert kernels.count("mla_sparse_prefill") == (0 if sparse_absorbed
                                                   else 2)
    assert kernels.count("mla_window_decode") == (3 if window_absorbed else 0)
    assert kernels.count("mla_window_prefill") == (0 if window_absorbed
                                                   else 3)
    assert not {"mla_decode", "mla_prefill", "paged_attention"} & set(kernels)
    assert kernels.count("gmm") == 12
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

    mem = compiled.memory_analysis()
    pool = sum(_nbytes(s) for s in cache.values())
    weights = sum(_nbytes(s) for s in jax.tree.leaves(params))
    assert mem.alias_size_in_bytes >= pool
    assert weights + pool + mem.temp_size_in_bytes < HBM - 2 ** 30, (
        weights / 2 ** 30, pool / 2 ** 30, mem.temp_size_in_bytes / 2 ** 30)
    print(f"[{N}x{C}] weights {weights / 2**30:.2f} GiB pool "
          f"{pool / 2**30:.2f} GiB temporaries "
          f"{mem.temp_size_in_bytes / 2**20:.1f} MiB")
