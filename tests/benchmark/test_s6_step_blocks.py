"""The S6 one-token step over the slots (``ops/selective_scan
.s6_step_slots``, PR 63) under the two blocks whose models run it, at
their tiny twins' sizes: the block's own replay — prompt in chunks, greedy
steps through ``[1, 1]`` forwards, the probe's fresh row — through an
engine whose one-token forwards hold the kernel ``s6_step`` (interpreted)
against one that gathers, steps and scatters: the same tokens, the same
rows of the view and the same state in the sequence's slot, to float32
round-off, and ``ssm_rows_stepped`` the rows that went through the kernel.
And Jamba's planted faults with the kernel in the forwards: a fresh row
that inherits its slot's state (``stale_slot``) still fails the cell's own
check, the engine as served still passes.

A file of its own: the blocks' test files are the benchmark's, and a PR
that claims a gain adds beside them."""

import os

import numpy as np
import pytest
from test_benchmark_runners import _read, checkout  # noqa: F401

from benchmark import manifest as mf

TWINS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "twins")
PROMPT, STEPS = 90, 5
#: block -> (configuration, cell)
BLOCKS = {"jamba": ("ai21-jamba2-3b", "ai21-jamba2-3b.chatrate"),
          "phi4flash": ("phi-4-mini-flash-reasoning",
                        "phi-4-mini-flash-reasoning.deepthink")}


def _twin(config):
    return _read(os.path.join(TWINS, "configs", config + ".json"))


def _traced(monkeypatch, kernel):
    """The hook as an engine built next will trace its forwards under,
    and the bucket rows of every trace of the kernel's function from now
    on."""
    from deepspeed_tpu.ops import selective_scan as s6

    monkeypatch.setattr(s6, "_FORCE_INTERPRET", kernel)
    rows = []
    inner = getattr(s6._step_in_kernel, "inner", s6._step_in_kernel)

    def counting(pool, layer, slots, *rest, **kw):
        rows.append(int(slots.shape[0]))
        return inner(pool, layer, slots, *rest, **kw)

    counting.inner = inner
    monkeypatch.setattr(s6, "_step_in_kernel", counting)
    return rows


def _replayed(name, kernel, monkeypatch):
    import jax.numpy as jnp

    from benchmark.model import seeded_params
    from deepspeed_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2, RaggedInferenceEngineConfig)
    from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig

    twin = _twin(BLOCKS[name][0])
    arch = twin["transformer_config"]
    model = CausalLM(TransformerConfig(**dict(arch, dtype=jnp.float32)))
    params = seeded_params(model, 3, jnp.float32)
    tokens = np.random.default_rng(4).integers(
        0, arch["vocab_size"], size=PROMPT).tolist()
    rows = _traced(monkeypatch, kernel)
    engine = InferenceEngineV2(
        model, params=params, config=RaggedInferenceEngineConfig(**dict(
            twin["engine"], compile_ahead=0)))
    (seen, at, got), = mf.find_module(mf.HERE, "blocks", name).replay(
        engine, 9, tokens, STEPS)
    sm = engine.state_manager
    state = np.asarray(sm.forward_cache["mamba1_ssm"])[
        :, sm.get_sequence(9).state_slot]
    scratch = np.asarray(sm.forward_cache["mamba1_ssm"])[:, sm.state_slots]
    totals = dict(engine.put_totals)
    engine.flush(9)
    assert sm.free_state_slots == sm.state_slots
    return seen, at, np.stack(got), state, scratch, totals, rows


@pytest.mark.parametrize("name", list(BLOCKS))
def test_the_replay_through_the_kernel_is_the_plain_forms(name, monkeypatch):
    plain = _replayed(name, False, monkeypatch)
    kernel = _replayed(name, True, monkeypatch)
    assert kernel[0] == plain[0] and len(plain[0]) == PROMPT + STEPS
    assert kernel[1] == plain[1]
    span = plain[2].max() - plain[2].min()
    assert np.abs(kernel[2] - plain[2]).max() < 5e-6 * span
    # every S6 layer's state of the sequence
    assert np.abs(plain[3]).max(axis=(1, 2)).min() > 0
    np.testing.assert_allclose(kernel[3], plain[3], rtol=2e-5, atol=1e-6)
    # the scratch slot: a padded row's, which the kernel never names
    np.testing.assert_array_equal(kernel[4], plain[4])
    # the replay's greedy steps, a [1, 1] forward each; the prompt's
    # chunks (and Jamba's probe's row) go through the chunked form
    for totals in (plain[5], kernel[5]):
        assert totals["ssm_rows_stepped"] == STEPS
        assert totals["ssm_chunk_tokens"] >= PROMPT
    # every body of S6 layers of the one-token program traced the kernel
    # at the bucket's rows; no chunk program did; the plain engine none
    assert plain[6] == []
    assert kernel[6] and set(kernel[6]) == {1}


def test_jambas_planted_faults_with_the_kernel_in_the_forwards(
        checkout, monkeypatch):  # noqa: F811
    """``python3 -m benchmark.jamba_controls`` at the twin's size with the
    one-token forwards through ``s6_step``: served passes, the S6 layers
    without their inner norms and a fresh row that is not zeroed — in the
    kernel, from the row's ``fresh`` — each fail."""
    from benchmark import jamba_controls

    rows = _traced(monkeypatch, True)
    info = mf.resolve(mf.load(checkout), BLOCKS["jamba"][1], checkout)
    prompt = np.random.default_rng(8).integers(0, 256, size=PROMPT).tolist()
    seen = {name: (expected, record["ok"], record.get("max_rel_err"))
            for name, expected, record in jamba_controls.run(info, 8, prompt)}
    assert list(seen) == ["served", "no_inner_norm", "stale_slot"]
    assert all(expected == ok for expected, ok, _ in seen.values()), seen
    assert min(seen["no_inner_norm"][2], seen["stale_slot"][2]) > 0.01
    assert rows and set(rows) == {1}
