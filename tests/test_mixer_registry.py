"""The mixer registry (``deepspeed_tpu/models/mixers``): what a kind
states about itself is what the façades that fold over the registry
return, the fleet's counter names are the registry's, and no module
outside the registry names a kind. Tiny widths, CPU; nothing is traced
beyond ``init``."""

import ast
import os

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.models import hybrid
from deepspeed_tpu.models.mixers import KINDS, PUT_TOTALS
from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig

PACKAGE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "deepspeed_tpu")
BASE = dict(vocab_size=64, hidden_size=32, intermediate_size=48,
            num_layers=2, num_heads=4, num_kv_heads=2, head_size=8,
            max_seq_len=64, norm="rmsnorm", activation="silu",
            position="rope", tie_embeddings=False, dtype=jnp.float32,
            qk_norm=True, attn_output_gate=True, attn_gate_proj=True)
LATENT = dict(q_lora_rank=16, kv_lora_rank=24, qk_nope_head_dim=8,
              qk_rope_head_dim=4, v_head_dim=8, attn_gate_headwise=True)
#: the fields a two-layer model of each kind needs beside ``BASE``
FIELDS = {
    "full": {},
    "linear": dict(linear_num_key_heads=2, linear_num_value_heads=4,
                   linear_key_head_dim=8, linear_value_head_dim=8),
    "window": dict(sliding_window=16),
    "latent": LATENT,
    "latent_sparse": dict(LATENT, index_n_heads=2, index_head_dim=8,
                          index_topk=4),
    "latent_window": dict(
        LATENT, sliding_window=16, swa_num_heads=2, swa_q_lora_rank=8,
        swa_kv_lora_rank=100, swa_qk_nope_head_dim=8,
        swa_qk_rope_head_dim=28, swa_v_head_dim=8, swa_rope_theta=5e4),
    "lightning": dict(lightning_num_heads=4, lightning_head_dim=8),
    "block_sparse": dict(block_kernel_size=4, block_kernel_stride=2,
                         block_select_size=8, block_topk=2,
                         block_window=16, block_dense_len=32),
    "mamba2": dict(mamba_num_heads=4, mamba_head_dim=8, mamba_state_size=16,
                   mamba_n_groups=2, mamba_chunk_size=16),
    "mamba1": dict(mamba1_inner_size=64, mamba1_state_size=4,
                   mamba1_dt_rank=2),
}
#: the kinds that read what an earlier run of layers hands on: no model
#: is made of one of them alone (``test_the_fed_kinds_...`` below)
FED = ("gmu", "cross")
BS, SLOTS = 8, 3


def test_the_cases_below_cover_the_registry():
    assert list(FIELDS) + list(FED) == list(KINDS)
    assert [kind for kind, mixer in KINDS.items() if mixer.takes] \
        == list(FED)


@pytest.mark.parametrize("kind", list(FIELDS))
def test_what_a_kind_states_is_what_the_facades_return(kind):
    """A two-layer model of one kind: the leaves ``init`` builds are the
    leaves ``specs`` names (and the model's tree is the slot's), and its
    pool and state leaves are ``kv_layouts``' and ``state_shapes``'."""
    cfg = TransformerConfig(**BASE, layer_pattern=(kind,), **FIELDS[kind])
    mixer = KINDS[kind]
    model = CausalLM(cfg)
    slot = jax.eval_shape(model.init, jax.random.PRNGKey(0))["layers"]["slot0"]
    specs = model.param_specs()["layers"]["slot0"]
    assert set(slot) == set(specs)
    own = set(mixer.specs(cfg))
    # the kind's leaves, beside the norms' and the dense MLP's
    assert set(slot) - own == {"attn_norm_w", "mlp_norm_w", "w_in",
                               "w_gate", "w_out"}
    assert all(leaf.shape[0] == cfg.num_periods == 2
               for leaf in slot.values())

    pool = mixer.pool(cfg, BS) if mixer.pool else {}
    assert cfg.kv_layouts(BS) == ((pool,) if pool else ())
    window = cfg.sliding_window if mixer.windowed else 0
    assert cfg.kv_groups() == (((window, 2),) if pool else ())
    assert cfg.is_latent == mixer.headless
    state = mixer.state(cfg, SLOTS) if mixer.state else {}
    assert hybrid.state_shapes(cfg, SLOTS) == state
    assert cfg.num_linear_layers == (2 if state else 0)
    assert all(shape[:2] == (2, SLOTS) for shape, _ in state.values())
    # a kind keeps a per-token cache or a state, and says which
    assert bool(pool) != bool(state)
    assert (mixer.scope is not None) == bool(pool)


def test_mamba1s_inner_norms_are_three_gains_and_one_field():
    """``mamba1_inner_norm`` (Jamba's form of the S6 layer): three gains
    over the step's projection, B and C beside the leaves the kind always
    had, named by ``specs``; off, the leaves are today's."""
    def slot(**more):
        cfg = TransformerConfig(**BASE, layer_pattern=("mamba1",),
                                **FIELDS["mamba1"], **more)
        model = CausalLM(cfg)
        leaves = jax.eval_shape(model.init,
                                jax.random.PRNGKey(0))["layers"]["slot0"]
        assert set(leaves) == set(model.param_specs()["layers"]["slot0"])
        return {name: leaf.shape for name, leaf in leaves.items()}

    off, on = slot(), slot(mamba1_inner_norm=True)
    assert off == slot(mamba1_inner_norm=False)
    assert {name: on[name] for name in set(on) - set(off)} == {
        "mamba1_dt_norm": (2, 2), "mamba1_b_norm": (2, 4),
        "mamba1_c_norm": (2, 4)}
    assert all(on[name] == off[name] for name in off)


def test_the_fed_kinds_keep_no_cache_and_say_what_they_read():
    """A model of runs: a state-space and a whole-context layer in a run
    of one period, the kinds that read them behind. Their leaves are what
    ``specs`` names; neither has a pool or a state of its own, and the
    pools count what is written."""
    runs = ((("mamba1", "full"), 1), (("gmu", "cross"), 2))
    cfg = TransformerConfig(**dict(
        BASE, num_layers=6, qk_norm=False, attn_output_gate=False,
        attn_gate_proj=False, rope_kinds=(), layer_runs=runs,
        **FIELDS["mamba1"]))
    assert cfg.layer_pattern == ("mamba1", "full", "gmu", "cross")
    assert cfg.runs == runs and cfg.run_feeds() == (("kv", "memory"), ())
    assert cfg.run_feeds(cached=True) == (("memory",), ())
    model = CausalLM(cfg)
    layers = jax.eval_shape(model.init, jax.random.PRNGKey(0))["layers"]
    specs = model.param_specs()["layers"]
    assert set(layers) == set(specs) == {
        "run0_slot0", "run0_slot1", "run1_slot0", "run1_slot1"}
    for kind, slot in (("gmu", "run1_slot0"), ("cross", "run1_slot1")):
        mixer = KINDS[kind]
        assert set(layers[slot]) == set(specs[slot])
        assert set(layers[slot]) - set(mixer.specs(cfg)) == {
            "attn_norm_w", "mlp_norm_w", "w_in", "w_gate", "w_out"}
        assert all(leaf.shape[0] == 2 for leaf in layers[slot].values())
        assert mixer.pool is None and mixer.state is None
        assert mixer.takes and mixer.scope
    assert KINDS["cross"].shares == "full" and KINDS["cross"].paged_walk
    assert set(KINDS["cross"].takes) <= set(KINDS["full"].hands)
    assert set(KINDS["gmu"].takes) <= set(KINDS["mamba1"].hands)
    # one whole-context layer is written, whoever reads it
    assert cfg.kv_groups() == ((0, 1),) and cfg.num_attn_layers == 1
    assert cfg.layers_of("cross") == 2 and cfg.num_linear_layers == 1
    assert cfg.exit_at() == (0, 1)
    with pytest.raises(AttributeError, match="runs"):
        cfg.num_periods


def test_the_fleets_counters_are_the_engines_own_and_the_registrys():
    from deepspeed_tpu.serving.metrics import serving_metrics
    from deepspeed_tpu.serving.replica import Replica

    own = ("forwards", "positions_computed", "tokens_valid", "puts_split",
           "forwards_qkv_fused", "forwards_merged", "forwards_held",
           "moe_rows_routed", "moe_rows_held", "kv_blocks_released")
    assert Replica._PUT_COUNTERS == own + PUT_TOTALS
    assert len(set(Replica._PUT_COUNTERS)) == len(Replica._PUT_COUNTERS)
    # every name a kind counts under is one of its totals or only in
    # ``last_put``; every total is declared to the fleet's registry
    assert set(PUT_TOTALS) == {name for mixer in KINDS.values()
                               for name in mixer.totals}
    declared = set(serving_metrics().names()["counters"])
    assert set(Replica._PUT_COUNTERS) <= declared
    assert all(mixer.count is not None for mixer in KINDS.values()
               if mixer.totals)


UNMISTAKABLE = ("latent_sparse", "latent_window", "block_sparse",
                "lightning", "linear_attn", "mamba2", "mamba1", "\"gmu\"")


def _docstrings(tree):
    for node in ast.walk(tree):
        if isinstance(node, (ast.Module, ast.ClassDef, ast.FunctionDef,
                             ast.AsyncFunctionDef)) and node.body \
                and isinstance(node.body[0], ast.Expr) \
                and isinstance(node.body[0].value, ast.Constant):
            yield id(node.body[0].value)


def test_no_module_outside_the_registry_names_a_kind():
    """A kind's name as a string literal (a docstring may say it), or the
    predicate ``has_kind``, nowhere under ``deepspeed_tpu/`` but in
    ``models/mixers/`` and ``ops/``: what reads a model's kinds folds
    over the registry."""
    found = []
    for folder, _, files in os.walk(PACKAGE):
        rel = os.path.relpath(folder, PACKAGE)
        if rel.startswith(("ops", os.path.join("models", "mixers"))):
            continue
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(folder, name)
            with open(path) as f:
                tree = ast.parse(f.read())
            said = set(_docstrings(tree))
            for node in ast.walk(tree):
                if isinstance(node, ast.Constant) \
                        and isinstance(node.value, str) \
                        and id(node) not in said \
                        and any(k in node.value for k in UNMISTAKABLE):
                    found.append((os.path.relpath(path, PACKAGE),
                                  node.lineno, node.value))
                if "has_kind" in (getattr(node, "id", None),
                                  getattr(node, "attr", None),
                                  getattr(node, "name", None)):
                    found.append((os.path.relpath(path, PACKAGE),
                                  node.lineno, "has_kind"))
    assert not found, found
