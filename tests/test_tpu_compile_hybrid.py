"""The hybrid serving forward compiled for a *described* TPU v5e at
Qwen3-Next-80B-A3B's widths (one period of four layers, a pool and slots
cut small — nothing runs, so sizes that only fill memory do not matter):
what the chip's compiler refuses shows here and not on the chip. The
paged kernel at head size 256 with 8 query heads a KV head, a 1,024-token
chunk cut into pieces of ``MAX_QUERY_ROWS`` rows; the grouped matmul as
the Pallas ``gmm`` (XLA's own ``ragged-dot`` custom calls carry no
``op_name``); every cache leaf — pool and recurrent state — aliased to
the output. See tests/test_tpu_compile.py for the method."""

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


@pytest.mark.parametrize("bucket", [(1, 1024), (8, 1)],
                         ids=lambda b: f"{b[0]}x{b[1]}")
def test_hybrid_forward_at_published_widths(v5e, bucket, monkeypatch):
    from deepspeed_tpu.inference.v2 import modules
    from deepspeed_tpu.inference.v2.paged_model import PagedCausalLM
    from deepspeed_tpu.models import hybrid
    from deepspeed_tpu.models import transformer as tr

    monkeypatch.setattr(pa, "_on_tpu", lambda: True)
    monkeypatch.setattr(modules, "on_tpu", lambda: True)
    monkeypatch.setattr(pallas_utils, "on_tpu", lambda: True)
    cfg = tr.TransformerConfig(
        vocab_size=75968, hidden_size=2048, intermediate_size=5120,
        num_layers=4, num_heads=16, num_kv_heads=2, head_size=256,
        max_seq_len=33280, norm="rmsnorm", norm_eps=1e-6,
        norm_zero_centered=True, activation="silu", position="rope",
        rope_pct=0.25, rope_theta=1e7, tie_embeddings=False,
        dtype=jnp.bfloat16,
        layer_pattern=("linear", "linear", "linear", "full"),
        attn_output_gate=True, qk_norm=True, linear_num_key_heads=16,
        linear_num_value_heads=32, linear_key_head_dim=128,
        linear_value_head_dim=128, linear_conv_kernel=4,
        moe_num_experts=512, moe_top_k=10, moe_dropless=True,
        moe_norm_topk=True, moe_held_experts=(0, 256),
        moe_intermediate_size=512, moe_shared_intermediate_size=512)
    model = tr.CausalLM(cfg)
    bs, NB, MB = 64, 1024, 520
    paged = PagedCausalLM(model, bs, MB, max_batch_tokens=1056)
    one = SingleDeviceSharding(v5e[0])

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    params = jax.tree.map(
        lambda a: spec(a.shape, jnp.bfloat16),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = {name: spec((1, NB, 2, bs, 256), jnp.bfloat16)
             for name in ("k", "v")}
    cache.update({name: spec(shape, dt) for name, (shape, dt)
                  in hybrid.state_shapes(cfg, 5).items()})
    N, C = bucket
    compiled = paged.forward.lower(
        params, cache, spec((N, C), jnp.int32), spec((N,), jnp.int32),
        spec((N,), jnp.int32), spec((N, MB), jnp.int32),
        spec((N,), jnp.int32)).compile()
    text = compiled.as_text()
    kernels = re.findall(r"%([a-z_\-]+)[.\d]* = [^\n]*tpu_custom_call", text)
    # one attention layer: the chunk's 8 x 1024 query rows in four pieces
    assert kernels.count("paged_attention") == (4 if C == 1024 else 1)
    # gate, up, down in each of the four layers, and nothing of XLA's own
    assert kernels.count("gmm") == 12
    assert not any(k.startswith("ragged") for k in kernels)
    scoped = re.findall(r'%gmm[.\d]* = [^\n]*op_name="([^"]*)"', text)
    assert scoped and all("/mlp/experts/" in s for s in scoped)

    def nbytes(s):
        return math.prod(s.shape) * jnp.dtype(s.dtype).itemsize

    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(nbytes(s) for s in cache.values())
    # no copy of the state tree (5 slots x 3 layers x 2 MiB) in the
    # temporaries of a decode step
    if C == 1:
        assert mem.temp_size_in_bytes < nbytes(cache["ssm"])
