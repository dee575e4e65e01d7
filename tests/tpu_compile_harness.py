"""What the tests that compile for a *described* TPU v5e share
(``tests/test_tpu_compile*.py``; tests/test_tpu_compile.py has the
method): the described chip, the persistent compile cache switched off
around them, and a configuration's paged forward built from its file at
the file's sizes and lowered for that chip. A file of those tests keeps
its buckets and its assertions; ``tests/test_marker_audit.py`` holds each
of them to taking the two fixtures from here, and ``tests/conftest.py``
starts the files that do first (they are the suite's heaviest).

Not a test file: nothing here is collected."""

import json
import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: one chip's memory: what a forward's temporaries share with the resident
#: weights and pools
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
    """An entry written for an unattached chip cannot be read back and
    warns on every later run."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


def bucket_id(value):
    """A case's id: a bucket ``(N, C)`` as ``NxC``, what is expected of
    it as it is."""
    return f"{value[0]}x{value[1]}" if isinstance(value, tuple) \
        else str(value)


def nbytes(s):
    return math.prod(s.shape) * jnp.dtype(s.dtype).itemsize


def kernels(text):
    """The Pallas kernels of an optimized HLO module, by name, one a
    call."""
    return re.findall(
        r"%([a-z_\-][a-z\d_\-]*?)[.\d]* = [^\n]*tpu_custom_call", text)


def stacked_group_sizes(text):
    """The ``dynamic_update_slice``s under ``mlp/experts`` in an optimized
    HLO module, by ``op_name``: a period's group sizes written into a
    vector over the groups of a whole stack of periods
    (``moe/grouped.dropless_moe_mlp(period=)``). A forward one period deep
    takes the path it always took and holds none."""
    return re.findall(
        r'op_name="([^"]*/mlp/experts/dynamic_update_slice)"', text)


def staged_projections(text, params):
    """The weights of an attention slot's projections (``wq``, its gate's
    ``wg``, ``wk``, ``wv`` of any slot of ``params``) that an optimized
    HLO module stages in front of their dots: every ``copy`` and every
    stand-alone slicing fusion whose result is as large as one of them
    out of its stack, either way round -- ``(instruction, dims, bytes)``
    each. Left free, a consumer that cuts a projection's output into
    heads has the compiler lay the *weight* out to fit, transposed, into
    fast memory (``mixers.base.held``); an asynchronous slice is a
    prefetch and is not among them."""
    shapes = set()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        if jax.tree_util.keystr(path).endswith(
                ("['wq']", "['wg']", "['wk']", "['wv']")):
            rows, cols = leaf.shape[-2:]
            shapes |= {f"{rows},{cols}", f"{cols},{rows}"}
    found = re.findall(
        r"%((?:copy|[\w\-]*slice[\w\-]*fusion)[.\d]*) = "
        r"bf16\[((?:1,)?(?:" + "|".join(sorted(shapes)) + r"))\]", text)
    return [(name, dims, 2 * math.prod(map(int, dims.split(","))))
            for name, dims in found]


def spec_on(device):
    one = SingleDeviceSharding(device)
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one)


def configuration(name, layers=None):
    """``benchmark/configs/<name>.json``'s model in bfloat16 (``layers``
    of it, where given) and its ``engine`` entries."""
    from deepspeed_tpu.models import transformer as tr

    with open(os.path.join(REPO, "benchmark", "configs",
                           name + ".json")) as f:
        body = json.load(f)
    arch = dict(body["transformer_config"], dtype=jnp.bfloat16)
    if layers:
        arch["num_layers"] = layers
    return tr.TransformerConfig(**arch), {
        k: v for k, v in body["engine"].items() if not k.startswith("_")}


def lowered(name, device, bucket, patch, layers=None, **engine):
    """The configuration's paged forward at ``bucket``, lowered for the
    described device over what the engine would hold at the file's sizes
    (``engine``: entries that replace the file's) -- the parameters in
    the serving layout, the first group's pool of ``kv_blocks`` and a
    further group's by the engine's own rule, a hybrid model's state a
    slot a sequence and one: ``(lowered, params, cache, cfg)``."""
    from deepspeed_tpu.inference.v2 import modules
    from deepspeed_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2, RaggedInferenceEngineConfig)
    from deepspeed_tpu.inference.v2.paged_model import (PagedCausalLM,
                                                        fuse_qkv)
    from deepspeed_tpu.models import hybrid
    from deepspeed_tpu.models import transformer as tr
    from deepspeed_tpu.ops import latent_attention as la
    from deepspeed_tpu.ops import paged_attention as pa
    from deepspeed_tpu.ops import pallas_utils

    # the CPU backend answers "not on TPU" and every kernel goes to
    # interpret mode or the XLA path: the test steers the switches -- not
    # an option of the program
    for module, switch in ((pa, "_on_tpu"), (la, "_on_tpu"),
                           (modules, "on_tpu"), (pallas_utils, "on_tpu")):
        patch.setattr(module, switch, lambda: True)
    cfg, sizes = configuration(name, layers)
    sizing = RaggedInferenceEngineConfig(**dict(sizes, **engine))
    N, C = bucket
    assert (C <= sizing.max_chunk_tokens
            and N <= sizing.max_ragged_sequence_count), bucket
    model = tr.CausalLM(cfg)
    bs = sizing.kv_block_size
    MB = -(-cfg.max_seq_len // bs)
    paged = PagedCausalLM(model, bs, MB,
                          max_batch_tokens=sizing.max_ragged_batch_size)
    spec = spec_on(device)
    params = jax.tree.map(
        lambda a: spec(a.shape, jnp.bfloat16),
        jax.eval_shape(lambda k: fuse_qkv(model.init(k)),
                       jax.random.PRNGKey(0)))
    groups = cfg.kv_groups()
    shell = type("E", (), {"config": sizing})()
    blocks = [sizing.kv_blocks] + [
        InferenceEngineV2.window_pool_blocks(shell, window)
        for window, _ in groups[1:]]
    cache = {}
    for g, ((_, n), layout) in enumerate(zip(groups, cfg.kv_layouts(bs))):
        for leaf, block in layout.items():
            cache[leaf + (str(g) if g else "")] = spec(
                (n, blocks[g]) + block, jnp.bfloat16)
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


def fits_beside(compiled, params, cache, bucket, headroom):
    """Every cache leaf is aliased to the output, and the weights, the
    pools and this forward's temporaries fit the chip with ``headroom``
    bytes to spare."""
    mem = compiled.memory_analysis()
    pool = sum(nbytes(s) for s in cache.values())
    weights = sum(nbytes(s) for s in jax.tree.leaves(params))
    assert mem.alias_size_in_bytes >= pool
    assert weights + pool + mem.temp_size_in_bytes < HBM - headroom, (
        weights / 2 ** 30, pool / 2 ** 30, mem.temp_size_in_bytes / 2 ** 30)
    print(f"[{bucket_id(bucket)}] weights {weights / 2**30:.2f} GiB pools "
          f"{pool / 2**30:.2f} GiB temporaries "
          f"{mem.temp_size_in_bytes / 2**20:.1f} MiB")
