"""A softmax-attention slot of the hybrid serving forward (``full``,
``window``, ``cross``: mixers/attention.py, cross.py) holds its
projections' outputs to rows in the narrow buckets (``mixers.base.held``)
— a hold on a layout, so the numbers are those of the forward that holds
nothing: logits, pools and state slots, bit for bit, on the tiny twins of
three blocks (``tests/benchmark/twins``, ``tests/benchmark/
qwen3_next_tiny.py``; CPU):

- ``trinity-large-preview``: a gate projection of its own (``wg``), q/k
  RMSNorm a head, a lead layer and two scanned periods, two layer groups;
- ``qwen3-next-80b-a3b``: the gate inside a ``wq`` twice as wide;
- ``phi-4-mini-flash-reasoning``: biases, the differential form, the
  exit's queries (a row's last position alone) and ``cross`` slots.

And the engine counts the forwards whose bucket was narrow
(``put_totals["forwards_held"]``). tests/test_rows_major.py holds the
same of the lightning and block-sparse slots."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.engine_v2 import (
    InferenceEngineV2, RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.paged_model import PagedCausalLM
from deepspeed_tpu.models import hybrid
from deepspeed_tpu.models.mixers import base
from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "benchmark"))
BS, SLOTS = 8, 5
#: block -> (the rows a narrow forward may have: a quarter of the twin's
#:           hidden size; the outputs its program's text holds to rows in
#:           a narrow bucket -- a scanned run's body is there once -- and
#:           in a wide one)
BLOCKS = {
    # q, g, k, v of the lead layer and of a period's four slots
    "trinity-large-preview": (8, 20, 0),
    # q (with its gate), k, v of a period's one attention layer
    "qwen3-next-80b-a3b": (8, 3, 0),
    # q, k, v of the window run's layer and of the exit, q of the cross
    # run's; behind the exit a wide chunk's rows are their last positions:
    # the exit's q and the cross layers' stay held
    "phi-4-mini-flash-reasoning": (16, 7, 2),
}


def _arch(name):
    if name == "qwen3-next-80b-a3b":
        from qwen3_next_tiny import TINY_QWEN3_NEXT
        return dict(TINY_QWEN3_NEXT["transformer_config"])
    with open(os.path.join(HERE, "benchmark", "twins", "configs",
                           name + ".json")) as f:
        return json.load(f)["transformer_config"]


def _model(name, dtype):
    cfg = TransformerConfig(**dict(_arch(name), dtype=dtype))
    return cfg, CausalLM(cfg)


@pytest.fixture(scope="module", params=sorted(BLOCKS))
def block(request):
    """A twin's name and float32 weights drawn here (matrices at 0.05,
    gains about 1, biases and the differential form's vectors too)."""
    _, model = _model(request.param, jnp.float32)
    rng = np.random.default_rng(61)

    def draw(path, leaf):
        gain = "norm" in jax.tree_util.keystr(path)
        return jnp.asarray(gain + (0.02 if gain else 0.05)
                           * rng.standard_normal(leaf.shape), leaf.dtype)

    return request.param, jax.tree_util.tree_map_with_path(
        draw, jax.eval_shape(model.init, jax.random.PRNGKey(0)))


def _forward(block, dtype, rows, chunk, start):
    """One forward of ``rows`` rows of ``chunk`` tokens from ``start``
    on (the last row one token short), over pools and slots that hold
    something already: (lowered text, logits, cache after)."""
    cfg, model = _model(block[0], dtype)
    params = jax.tree.map(lambda a: a.astype(dtype), block[1])
    MB = cfg.max_seq_len // BS
    paged = PagedCausalLM(model, BS, MB)
    rng = np.random.default_rng(5)
    groups = cfg.kv_groups()
    cache = {}
    for g, ((_, n), layout) in enumerate(zip(groups, cfg.kv_layouts(BS))):
        for leaf, blk in layout.items():
            cache[leaf + (str(g) if g else "")] = jnp.asarray(
                0.1 * rng.standard_normal((n, rows * MB) + blk), dtype)
    for leaf, (shape, dt) in hybrid.state_shapes(cfg, SLOTS).items():
        cache[leaf] = jnp.asarray(0.1 * rng.standard_normal(shape), dt)
    table = jnp.arange(rows * MB, dtype=jnp.int32).reshape(rows, MB)
    n_tokens = np.full((rows,), chunk, np.int32)
    n_tokens[-1] = max(chunk - 1, 1)
    args = [jnp.asarray(rng.integers(0, cfg.vocab_size, (rows, chunk)),
                        jnp.int32),
            jnp.full((rows,), start, jnp.int32), jnp.asarray(n_tokens),
            table if len(groups) == 1
            else jnp.stack([table] * len(groups))]
    if cfg.is_hybrid and cfg.num_linear_layers:
        args.append(jnp.arange(rows, dtype=jnp.int32))
    lowered = paged.forward.lower(params, cache, *args)
    logits, cache = lowered.compile()(params, cache, *args)
    return lowered.as_text(), np.asarray(logits.astype(jnp.float32)), \
        jax.tree.map(lambda a: np.asarray(a.astype(jnp.float32)), cache)


@pytest.mark.parametrize("rows,chunk,start,dtype", [
    (3, 1, 21, jnp.float32), (3, 1, 21, jnp.bfloat16),
    (2, 4, 16, jnp.float32), (2, 4, 16, jnp.bfloat16),
    (1, 32, 0, jnp.float32)],
    ids=["a-step-of-three-rows-float32", "a-step-of-three-rows-bfloat16",
         "a-mixed-bucket-float32", "a-mixed-bucket-bfloat16",
         "a-wide-chunk-float32"])
def test_held_to_rows_or_not_the_numbers_are_the_same(
        block, rows, chunk, start, dtype, monkeypatch):
    name = block[0]
    cfg, _ = _model(name, dtype)
    narrow_rows, held_narrow, held_wide = BLOCKS[name]
    text, logits, cache = _forward(block, dtype, rows, chunk, start)
    narrow = rows * chunk <= narrow_rows
    assert narrow == base.narrow(cfg, rows * chunk)
    assert text.count("@LayoutConstraint") == (held_narrow if narrow
                                               else held_wide)
    monkeypatch.setattr(base, "rows_major", lambda y: y)
    free_text, want, free = _forward(block, dtype, rows, chunk, start)
    assert "@LayoutConstraint" not in free_text
    assert np.isfinite(want).all() and np.ptp(want) > 0
    np.testing.assert_array_equal(logits, want)
    assert set(cache) == set(free)
    for leaf, value in free.items():
        np.testing.assert_array_equal(cache[leaf], value, err_msg=leaf)


def test_the_engine_counts_the_forwards_it_holds(block):
    """``forwards_held`` beside ``forwards``: a prompt of 24 tokens runs
    in a wide bucket, the two one-token steps behind it in a narrow one."""
    cfg, model = _model(block[0], jnp.float32)
    eng = InferenceEngineV2(model, params=block[1],
                            config=RaggedInferenceEngineConfig(
        kv_block_size=BS, kv_blocks=64, max_ragged_sequence_count=4,
        max_chunk_tokens=32, max_ragged_batch_size=64))
    assert eng.put_totals["forwards_held"] == 0
    eng.put([7], [list(range(3, 27))])
    assert eng.last_put["bucket_chunk"] >= 24
    assert not base.narrow(cfg, eng.last_put["bucket_seqs"]
                           * eng.last_put["bucket_chunk"])
    assert eng.put_totals["forwards_held"] == 0
    for token in (5, 9):
        eng.put([7], [[token]])
        assert eng.last_put["bucket_chunk"] == 1
    assert eng.put_totals["forwards"] == 3
    assert eng.put_totals["forwards_held"] == 2
    assert "forwards_held" not in eng.last_put
