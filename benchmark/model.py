"""From a configuration's file to the program's model objects.

The file holds the published ``config.json`` keys (what the contract
compares) and, under ``transformer_config``, the same architecture as
``TransformerConfig`` fields — data, so a new model of a block that is
there needs no code. ``check_consistent`` holds the two views together;
a block module adds the keys only its kind of model publishes
(``PUBLISHED_TO_FIELD`` in ``blocks/<block>.py``)."""

from __future__ import annotations

#: published key -> TransformerConfig field, where both state one number
PUBLISHED_TO_FIELD = {
    "hidden_size": "hidden_size",
    "intermediate_size": "intermediate_size",
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "vocab_size": "vocab_size",
    "rotary_pct": "rope_pct",
    "rope_theta": "rope_theta",
    "rotary_emb_base": "rope_theta",
    "sliding_window": "sliding_window",
    "layer_norm_eps": "norm_eps",
    "rms_norm_eps": "norm_eps",
    "use_parallel_residual": "parallel_residual",
    "tie_word_embeddings": "tie_embeddings",
}


def check_consistent(config: dict, block=None) -> None:
    arch = config["transformer_config"]
    pairs = dict(PUBLISHED_TO_FIELD,
                 **getattr(block, "PUBLISHED_TO_FIELD", {}))
    for key, field in pairs.items():
        if key in config and field in arch and config[key] != arch[field]:
            raise ValueError(f"{key}={config[key]!r} in the file, but "
                             f"transformer_config.{field}={arch[field]!r}")
    if arch["max_seq_len"] > config["max_position_embeddings"]:
        raise ValueError("positions run exceed the published positions")


def transformer_config(info: dict, **overrides):
    """The program's ``TransformerConfig`` for the cell's configuration
    (``info`` is ``manifest.resolve``'s)."""
    import jax.numpy as jnp

    from deepspeed_tpu.models.transformer import TransformerConfig

    config = info["config"]
    check_consistent(config, info["block"])
    fields = dict(config["transformer_config"])
    fields["dtype"] = jnp.dtype(fields["dtype"])
    fields.update(overrides)
    return TransformerConfig(**fields)


def seeded_params(model, seed: int, dtype):
    """Random weights on the device in one jitted call from the seed, in
    the type they are served in. Norm gains and every bias are perturbed
    too (``model.init`` leaves them at 1 and 0), so that a forward that
    dropped a bias or a gain would not agree with the reference."""
    import jax
    import jax.numpy as jnp

    def is_affine(path):
        last = str(getattr(path[-1], "key", path[-1]))
        return last.endswith("_b") or last == "b" or "norm" in "/".join(
            str(getattr(p, "key", p)) for p in path)

    def make(key):
        k_init, k_noise = jax.random.split(key)
        params = model.init(k_init)
        flat, tree = jax.tree_util.tree_flatten_with_path(params)
        keys = jax.random.split(k_noise, len(flat))
        out = []
        for (path, leaf), k in zip(flat, keys):
            if is_affine(path):
                leaf = leaf + 0.02 * jax.random.normal(k, leaf.shape)
            out.append(leaf.astype(dtype))
        return jax.tree_util.tree_unflatten(tree, out)

    return jax.jit(make)(jax.random.PRNGKey(seed))
