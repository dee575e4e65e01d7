"""The serving forward of lightning and block-sparse layers holds
projections' outputs to rows (``mixers.base.rows_major``, ``held``):
a hold on a layout, so the numbers are those of the forward that holds
nothing — logits, pools and state slots, on the tiny twin of
``minicpm-sala`` (``tests/benchmark/twins``; CPU, float32)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.paged_model import PagedCausalLM
from deepspeed_tpu.models import hybrid
from deepspeed_tpu.models.mixers import base
from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig

HERE = os.path.dirname(os.path.abspath(__file__))
BS, SLOTS = 8, 3


@pytest.fixture(scope="module")
def twin():
    """One period of the twin (a block-sparse layer and three lightning
    layers) with weights drawn here: matrices at 0.05, gains about 1."""
    with open(os.path.join(HERE, "benchmark", "twins", "configs",
                           "minicpm-sala.json")) as f:
        arch = json.load(f)["transformer_config"]
    cfg = TransformerConfig(**dict(arch, num_layers=4, dtype=jnp.float32))
    model = CausalLM(cfg)
    rng = np.random.default_rng(3)

    def draw(path, leaf):
        gain = "norm" in jax.tree_util.keystr(path)
        return jnp.asarray(gain + (0.02 if gain else 0.05)
                           * rng.standard_normal(leaf.shape), leaf.dtype)

    params = jax.tree_util.tree_map_with_path(
        draw, jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    return cfg, model, params


def _forward(twin, rows, chunk, start):
    """One forward of ``rows`` rows of ``chunk`` tokens from ``start``
    on, over pools and slots that hold something already: (lowered
    text, logits, cache after)."""
    cfg, model, params = twin
    MB = cfg.max_seq_len // BS
    paged = PagedCausalLM(model, BS, MB)
    rng = np.random.default_rng(5)
    cache = {}
    for leaf, block in cfg.kv_layouts(BS)[0].items():
        cache[leaf] = jnp.asarray(0.1 * rng.standard_normal(
            (cfg.kv_groups()[0][1], rows * MB) + block), jnp.float32)
    for leaf, (shape, dt) in hybrid.state_shapes(cfg, SLOTS).items():
        cache[leaf] = jnp.asarray(0.1 * rng.standard_normal(shape), dt)
    args = (jnp.asarray(rng.integers(0, cfg.vocab_size, (rows, chunk)),
                        jnp.int32),
            jnp.full((rows,), start, jnp.int32),
            jnp.full((rows,), chunk, jnp.int32),
            jnp.arange(rows * MB, dtype=jnp.int32).reshape(rows, MB),
            jnp.arange(rows, dtype=jnp.int32))
    lowered = paged.forward.lower(params, cache, *args)
    logits, cache = lowered.compile()(params, cache, *args)
    return lowered.as_text(), np.asarray(logits), \
        jax.tree.map(np.asarray, cache)


@pytest.mark.parametrize("rows,chunk,start,held", [
    (2, 1, 70, 16), (1, 16, 64, 16), (1, 64, 0, 9)],
    ids=["a-step-of-two-rows", "a-narrow-chunk", "a-wide-chunk"])
def test_held_to_rows_or_not_the_numbers_are_the_same(
        twin, rows, chunk, start, held, monkeypatch):
    """Three lightning layers and a block-sparse one: q, k, v and the
    gate of each up to a quarter of the hidden size in rows, the
    lightning layers' q, k and v past it."""
    text, logits, cache = _forward(twin, rows, chunk, start)
    assert text.count("@LayoutConstraint") == held
    monkeypatch.setattr(base, "rows_major", lambda y: y)
    free_text, want, free = _forward(twin, rows, chunk, start)
    assert "@LayoutConstraint" not in free_text
    assert np.isfinite(want).all() and np.ptp(want) > 0
    np.testing.assert_array_equal(logits, want)
    assert set(cache) == set(free)
    for leaf, value in free.items():
        np.testing.assert_array_equal(cache[leaf], value, err_msg=leaf)


def test_what_is_held_by_kind_and_rows(twin, monkeypatch):
    cfg = twin[0]
    assert cfg.hidden_size == 64
    monkeypatch.setattr(base, "rows_major", lambda y: "held")

    def held(always, rows):
        # a projection's output is asked its own rows: [1, rows, out]
        out = jax.ShapeDtypeStruct((1, rows, 8), jnp.float32)
        hold = base.held(cfg, always)
        return "".join(n for n in "qkvg" if hold(n, out) == "held")

    # a lightning layer's q, k and v always; a block-sparse layer's none
    assert [held("qkv", rows) for rows in (1, 16, 17, 64)] \
        == ["qkvg", "qkvg", "qkv", "qkv"]
    assert [held("", rows) for rows in (1, 16, 17, 64)] \
        == ["qkvg", "qkvg", "", ""]
