"""The names inside the program (docs/OBSERVABILITY.md "XLA alignment"):
every operation of the paged forward and of the train step carries, in
its HLO ``op_name``, the scope of the program part that caused it. The
names are read by trace readers (``benchmark/scopes.py``) and by people in
Perfetto; a refactor that drops one should fail here, not on the chip."""

import re

import jax
import numpy as np
import pytest

BLOCK = ("attn_norm", "qkv", "attend", "attn_out", "mlp")


def tiny_engine():
    from deepspeed_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2, RaggedInferenceEngineConfig)
    from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig

    cfg = TransformerConfig(vocab_size=128, hidden_size=64,
                            intermediate_size=128, num_layers=2, num_heads=2,
                            max_seq_len=128, norm="rmsnorm",
                            activation="silu", position="rope")
    return InferenceEngineV2(CausalLM(cfg), config=RaggedInferenceEngineConfig(
        max_ragged_batch_size=128, max_ragged_sequence_count=4,
        max_chunk_tokens=32, kv_blocks=64, kv_block_size=8,
        max_tracked_sequences=16))


def _op_names(lowered):
    """Every distinct scope path in a lowering's debug locations."""
    return set(re.findall(r'loc\("([^"]+)"', lowered.as_text(debug_info=True)))


def _has(names, *parts):
    """Some operation's path holds ``parts`` in that order."""
    pattern = re.compile(".*".join(re.escape(p) for p in parts))
    return any(pattern.search(n) for n in names)


@pytest.mark.parametrize("verify_width", [0, 2])
def test_paged_forward_carries_the_scope_vocabulary(verify_width):
    eng = tiny_engine()
    tokens = np.zeros((2, 4), np.int32)
    args = (eng.params, eng.state_manager.kv_cache, tokens,
            np.zeros((2,), np.int32), np.full((2,), 4, np.int32),
            np.zeros((2, eng.paged.max_blocks_per_seq), np.int32))
    fn = eng.paged.forward_verify if verify_width else eng.paged.forward
    kw = {"verify_width": verify_width} if verify_width else {}
    lowered = fn.lower(*args, **kw)
    assert "jit__forward" in lowered.as_text()      # the module's name
    names = _op_names(lowered)
    for top in ("embed", "layers", "final_norm", "logits", "kv_write"):
        assert _has(names, top), top
    # the block's scopes sit under the scan (a nested function's locations
    # restart at the call, so "layers" is not in the same string here; in
    # HLO op_name the two are joined)
    for scope in BLOCK + ("kv_write",):
        assert any(n.startswith(scope + "/") for n in names), scope
    assert _has(names, "kv_write/", "scatter")
    assert _has(names, "attend/")


def test_train_step_carries_its_scopes_and_the_pass_markers():
    """micro: loss_and_grad, grad_accumulate; under loss_and_grad JAX's
    own prefixes tell forward (jvp), backward (transpose(jvp)) and the
    recomputed forward (checkpoint/rematted_computation) apart. update:
    grad_norm_clip, optimizer."""
    import deepspeed_tpu
    from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig

    cfg = TransformerConfig(vocab_size=64, hidden_size=32,
                            intermediate_size=64, num_layers=2, num_heads=2,
                            max_seq_len=64, norm="rmsnorm",
                            activation="silu", position="rope", remat=True)
    engine, *_ = deepspeed_tpu.initialize(model=CausalLM(cfg), config={
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "steps_per_print": 10**9, "mesh": {"data": -1, "fsdp": 1}})
    gb = engine.topology.get_data_parallel_world_size()
    batch = engine._device_batch(
        {"input_ids": np.zeros((gb, 17), np.int32)})
    micro = engine._micro_fn.lower(engine.state, batch, jax.random.PRNGKey(0))
    assert "jit_micro" in micro.as_text()
    names = _op_names(micro)
    assert _has(names, "jit(micro)/grad_accumulate/")
    for scope in ("embed", "layers", "final_norm", "logits", "loss"):
        assert _has(names, f"jit(micro)/loss_and_grad/jvp({scope})"), scope
        assert _has(names, "jit(micro)/loss_and_grad/transpose(jvp("
                    f"{scope}))"), scope
    for scope in BLOCK:
        assert any(n.startswith(scope + "/") for n in names), scope
        assert _has(names, "checkpoint/rematted_computation/" + scope), scope
        assert any(n.startswith(f"checkpoint/{scope}/") for n in names), scope
    update = engine._update_fn.lower(engine.state)
    assert "jit_update" in update.as_text()
    names = _op_names(update)
    assert _has(names, "jit(update)/grad_norm_clip/")
    assert _has(names, "jit(update)/optimizer/")
