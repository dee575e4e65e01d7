"""The sparse FFN of a hybrid block (``moe/grouped.dropless_moe_mlp``,
``models/hybrid.moe_ffn``): top-k over all experts, computed for a held
share — the shares add up to the uncut layer."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import hybrid
from deepspeed_tpu.models.transformer import TransformerConfig
from deepspeed_tpu.moe.grouped import dropless_moe_mlp

N, H, M, E, K = 24, 16, 12, 8, 3


def weights(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return {"tokens": jax.random.normal(ks[0], (N, H)),
            "logits": 2.0 * jax.random.normal(ks[1], (N, E)),
            "w_in": 0.3 * jax.random.normal(ks[2], (E, H, M)),
            "w_gate": 0.3 * jax.random.normal(ks[3], (E, H, M)),
            "w_out": 0.3 * jax.random.normal(ks[4], (E, M, H))}


#: the gated forms: what the gate goes through, in float64
GATES = {"silu": lambda a: a / (1 + np.exp(-a)),
         "reglu": lambda a: np.maximum(a, 0.0)}


def by_hand(w, top_k, renormalize, lo=0, n=E, valid=None, activation="silu"):
    """Every (token, choice) pair in a Python loop."""
    probs = np.asarray(jax.nn.softmax(w["logits"], -1), np.float64)
    out = np.zeros((N, H))
    for t in range(N):
        if valid is not None and not valid[t]:
            continue
        top = np.argsort(-probs[t])[:top_k]
        gate = probs[t, top] / (probs[t, top].sum() if renormalize else 1.0)
        x = np.asarray(w["tokens"][t], np.float64)
        for e, g in zip(top, gate):
            if lo <= e < lo + n:
                a = x @ np.asarray(w["w_gate"][e], np.float64)
                h = GATES[activation](a) * (x @ np.asarray(w["w_in"][e],
                                                           np.float64))
                out[t] += g * (h @ np.asarray(w["w_out"][e], np.float64))
    return out


def share(w, lo, n, activation="silu", **kw):
    out, _ = dropless_moe_mlp(
        w["tokens"], w["logits"], w["w_in"][lo:lo + n], w["w_out"][lo:lo + n],
        w["w_gate"][lo:lo + n], activation=activation, held=(lo, n), **kw)
    return np.asarray(out)


@pytest.mark.parametrize("top_k", [1, 2, 3, 8])
@pytest.mark.parametrize("renormalize", [False, True])
def test_top_k_against_a_loop_over_the_pairs(top_k, renormalize):
    w = weights(top_k)
    got, aux = dropless_moe_mlp(w["tokens"], w["logits"], w["w_in"],
                                w["w_out"], w["w_gate"], activation="silu",
                                top_k=top_k, renormalize=renormalize)
    np.testing.assert_allclose(got, by_hand(w, top_k, renormalize),
                               atol=2e-5)
    assert np.isfinite(float(aux))


@pytest.mark.parametrize("activation", ["silu", "reglu"])
@pytest.mark.parametrize("parts", [1, 2, 4, 8])
def test_the_shares_add_up_to_the_uncut_layer(parts, activation):
    """The experts split in ``parts`` holders: each routes over all of
    them and computes its own; the parts summed are the whole layer —
    for either gated form of an expert."""
    w = weights(parts)
    whole = share(w, 0, E, activation, top_k=K, renormalize=True)
    n = E // parts
    held = [share(w, lo, n, activation, top_k=K, renormalize=True)
            for lo in range(0, E, n)]
    by = lambda *a: by_hand(w, K, True, *a, activation=activation)  # noqa
    np.testing.assert_allclose(sum(held), whole, atol=2e-5)
    np.testing.assert_allclose(whole, by(), atol=2e-5)
    if parts > 1:       # and no part is the whole
        assert np.abs(held[0] - whole).max() > 1e-3
    np.testing.assert_allclose(held[-1], by(E - n, n), atol=2e-5)
    if activation != "silu":        # the two gated forms are two layers
        silu = share(w, 0, E, top_k=K, renormalize=True)
        assert np.abs(whole - silu).max() > 1e-3


@pytest.mark.parametrize("activation", ["relu", "relu2", "gelu"])
def test_a_gate_that_no_form_would_use_is_refused(activation):
    """An ungated activation handed ``w_gate`` used to drop it without a
    word; a gated one without it ran a gelu. Both are refused."""
    w = weights()
    with pytest.raises(ValueError, match="ungated and would drop w_gate"):
        dropless_moe_mlp(w["tokens"], w["logits"], w["w_in"], w["w_out"],
                         w["w_gate"], activation=activation)
    got, _ = dropless_moe_mlp(w["tokens"], w["logits"], w["w_in"],
                              w["w_out"], None, activation=activation)
    assert np.isfinite(np.asarray(got)).all()
    for gated in ("silu", "reglu"):
        with pytest.raises(ValueError, match="gated and needs w_gate"):
            dropless_moe_mlp(w["tokens"], w["logits"], w["w_in"],
                             w["w_out"], None, activation=gated)


def test_renormalised_weights_sum_to_one():
    """With one expert that is the identity-free constant map, the output
    is the sum of the top-k weights: 1 when renormalised, less when not."""
    w = weights(5)
    probs = jax.nn.softmax(w["logits"], -1)
    top = jnp.sort(probs, -1)[:, -K:].sum(-1)
    # every expert the same linear map => out = (sum of weights) * f(x)
    same = {k: jnp.broadcast_to(w[k][:1], w[k].shape)
            for k in ("w_in", "w_gate", "w_out")}
    one = by_hand(dict(w, **same), 1, True)         # weight 1, one expert
    for renorm, total in ((True, np.ones(N)), (False, np.asarray(top))):
        got, _ = dropless_moe_mlp(w["tokens"], w["logits"], same["w_in"],
                                  same["w_out"], same["w_gate"],
                                  activation="silu", top_k=K,
                                  renormalize=renorm)
        np.testing.assert_allclose(got, one * total[:, None], atol=2e-5)


@pytest.mark.parametrize("max_rows", [None, 10, 24, 40])
def test_padded_rows_reach_no_expert(max_rows):
    w = weights(7)
    valid = np.zeros(N, bool)
    valid[[0, 3, 4, 9, 10, 11, 17, 20, 22, 23]] = True
    got = share(w, 2, 4, top_k=K, renormalize=True, valid=jnp.asarray(valid),
                max_rows=max_rows)
    assert np.array_equal(got[~valid], np.zeros((N - 10, H)))
    np.testing.assert_allclose(got, by_hand(w, K, True, 2, 4, valid),
                               atol=2e-5)
    # garbage (even non-finite) in a padded row changes nothing
    dirty = dict(w, tokens=w["tokens"].at[1].set(jnp.inf))
    again = share(dirty, 2, 4, top_k=K, renormalize=True,
                  valid=jnp.asarray(valid), max_rows=max_rows)
    np.testing.assert_allclose(again[valid], got[valid], atol=1e-6)


def test_a_held_range_must_match_its_weights():
    w = weights()
    with pytest.raises(ValueError, match="held"):
        dropless_moe_mlp(w["tokens"], w["logits"], w["w_in"][:4],
                         w["w_out"][:4], w["w_gate"][:4], held=(0, 3))


CFG = TransformerConfig(
    vocab_size=64, hidden_size=H, intermediate_size=32, num_layers=2,
    num_heads=2, head_size=8, max_seq_len=64, norm="rmsnorm",
    position="rope", activation="silu", tie_embeddings=False,
    layer_pattern=("linear", "full"), linear_num_key_heads=1,
    linear_num_value_heads=2, linear_key_head_dim=8, linear_value_head_dim=8,
    moe_num_experts=E, moe_top_k=K, moe_dropless=True, moe_norm_topk=True,
    moe_intermediate_size=M, moe_shared_intermediate_size=10)


def layer_params(cfg, key):
    lp = hybrid.init_slot(cfg, "full", key, 1)
    return {k: v[0] for k, v in lp.items()}


@pytest.mark.parametrize("parts", [2, 4])
def test_the_shared_expert_is_counted_once(parts):
    """``moe_ffn`` adds the shared expert to every holder's part, as the
    deployment computes it on every chip; the parts' routed sums plus ONE
    shared expert are the uncut layer."""
    whole_cfg = dataclasses.replace(CFG, moe_held_experts=None)
    lp = layer_params(whole_cfg, jax.random.PRNGKey(3))
    lp["router_wg"] = 3.0 * lp["router_wg"]
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 9, H))
    whole, _ = hybrid.moe_ffn(whole_cfg, x, lp)
    no_shared = dataclasses.replace(whole_cfg, moe_shared_intermediate_size=0)
    routed, _ = hybrid.moe_ffn(no_shared, x, lp)
    shared = whole - routed
    assert float(jnp.abs(shared).max()) > 1e-4
    n = E // parts
    total = 0
    for lo in range(0, E, n):
        cfg = dataclasses.replace(CFG, moe_held_experts=(lo, n))
        part = dict(lp, **{k: lp[k][lo:lo + n]
                           for k in ("w_in", "w_gate", "w_out")})
        y, _ = hybrid.moe_ffn(cfg, x, part)
        total = total + (y - shared)
    np.testing.assert_allclose(total + shared, whole, atol=2e-5)


@pytest.mark.parametrize("activation,gated", [("silu", True),
                                              ("reglu", True),
                                              ("relu2", False)])
def test_every_gated_form_draws_and_shards_a_gate(activation, gated):
    cfg = dataclasses.replace(CFG, moe_activation=activation)
    lp = hybrid.init_slot(cfg, "full", jax.random.PRNGKey(0), 2)
    assert ("w_gate" in lp) == ("shared_w_gate" in lp) == gated
    assert set(hybrid.slot_specs(cfg, "full")) == set(lp)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 5, H))
    y, _ = hybrid.moe_ffn(cfg, x, {k: v[0] for k, v in lp.items()})
    assert y.shape == x.shape and np.isfinite(np.asarray(y)).all()


@pytest.mark.parametrize("wrong", [
    dict(moe_activation="relu"), dict(moe_activation="gelu"),
    dict(moe_router_input="attention"),
    dict(moe_router_input="layer", layer_pattern=("linear", None),
         layer_ffn=(False, True)),
    dict(moe_router_input="layer", layer_ffn=(True, False)),
    dict(moe_router_input="layer", moe_num_experts=0,
         moe_shared_intermediate_size=0)],
    ids=["dense-relu", "gelu", "unknown-input", "a-position-without-mixer",
         "a-position-without-ffn", "no-experts"])
def test_what_the_sparse_ffn_cannot_be_is_refused_with_a_sentence(wrong):
    with pytest.raises(ValueError, match="moe_activation is|"
                                         "moe_router_input is"):
        dataclasses.replace(CFG, **wrong)


def test_the_router_may_read_the_layers_input_only_in_a_hybrid_block():
    with pytest.raises(ValueError, match="belong to a layer_pattern"):
        TransformerConfig(moe_router_input="layer")
    assert dataclasses.replace(CFG, moe_router_input="layer",
                               moe_activation="reglu").moe_router_input \
        == "layer"


def test_early_logits_replace_the_ffns_own_router():
    """``moe_ffn`` given ``router_logits`` makes no router matmul of its
    own: the logits of its normed input, handed in, give its own answer;
    other logits give another."""
    cfg = dataclasses.replace(CFG, moe_shared_intermediate_size=0)
    lp = layer_params(cfg, jax.random.PRNGKey(3))
    lp["router_wg"] = 3.0 * lp["router_wg"]
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 9, H))
    own, _ = hybrid.moe_ffn(cfg, x, lp)
    logits = x.reshape(-1, H) @ lp["router_wg"]
    same, _ = hybrid.moe_ffn(cfg, x, lp, router_logits=logits)
    np.testing.assert_array_equal(np.asarray(own), np.asarray(same))
    other, _ = hybrid.moe_ffn(cfg, x, lp, router_logits=logits[::-1])
    assert np.abs(np.asarray(other) - np.asarray(own)).max() > 1e-4
    def lowered(early):
        return jax.jit(lambda x, lg: hybrid.moe_ffn(
            cfg, x, lp, router_logits=lg if early else None)[0]
        ).lower(x, logits).as_text(debug_info=True)

    assert "/router/" in lowered(False)
    assert "/router/" not in lowered(True)


def test_init_holds_the_share_and_routes_over_all():
    cfg = dataclasses.replace(CFG, moe_held_experts=(4, 2))
    lp = hybrid.init_slot(cfg, "linear", jax.random.PRNGKey(0), 3)
    assert lp["router_wg"].shape == (3, H, E)
    assert lp["w_in"].shape == (3, 2, H, M)
    assert lp["w_out"].shape == (3, 2, M, H)
    assert lp["shared_w_in"].shape == (3, H, 10)
    assert set(hybrid.slot_specs(cfg, "linear")) == set(lp)
    full = hybrid.init_slot(cfg, "full", jax.random.PRNGKey(0), 3)
    assert set(hybrid.slot_specs(cfg, "full")) == set(full)
