"""Where a configuration's serving tolerances (its ``check`` group) come
from, measured and not asserted:

    python3 -m benchmark.tolerance --config <name> [--seed 1] [--prompt-tokens 200]
                                   [--variants served,fp8_weights]

runs the cell's own correctness check (``serve_runner.check_logits``: the
engine through ``engine.put`` the way the configuration's block type
generates, its ``replay``, against the reference of the same block,
``blocks/<block>.py``) on one seeded prompt, three times, or as many as
``--variants`` names (a build that does not fit the device is left out:
the float32 one holds 4 bytes a parameter), in this order:

``float32``       engine and weights in float32. What is left is what the
                  two implementations do differently — it must be tiny,
                  or the tolerance would be hiding a fault.
``served``        the configuration's own type: the rounding's share.
                  The tolerance sits above this.
``fp8_weights``   the served type, with the engine's weights rounded
                  through float8_e4m3 under a scale per tensor, so that
                  they keep 3 bits of mantissa where the served type has
                  7 (the reference keeps the unrounded ones): a lower
                  precision. The tolerance sits below this, or the check
                  could not see it.

One JSON line each. It runs wherever JAX runs, so it can be rehearsed on
the CPU (logit disagreements are not device metrics; say where they were
read). On the CPU the engine's paged attention is the XLA formulation, on
the chip the Pallas kernel.
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np

from . import manifest as mf
from . import serve_runner as sr


VARIANTS = ("float32", "served", "fp8_weights")


def variants(info: dict, seed: int, prompt, kv_blocks: int,
             wanted=VARIANTS):
    """(name, check_logits record) for the builds named in ``wanted``."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2

    info = dict(info, config=dict(
        info["config"],
        engine=dict(info["config"]["engine"], kv_blocks=kv_blocks)))
    check = info["config"]["check"]

    def measure(engine, params):
        return sr.check_logits(engine, params, info, [prompt],
                               check["decode_steps"], check["tolerance"],
                               check["rms_tolerance"])

    if "float32" in wanted:
        cfg, params, engine = sr.build(info, seed, {"dtype": jnp.float32})
        yield "float32", measure(engine, params)
        del params, engine
    if set(wanted) <= {"float32"}:
        return
    cfg, params, engine = sr.build(info, seed)
    if "served" in wanted:
        yield "served", measure(engine, params)
    if "fp8_weights" not in wanted:
        return

    def through_fp8(a):
        wide = a.astype(jnp.float32)
        scale = 448.0 / jnp.max(jnp.abs(wide))      # e4m3's largest: 448
        return ((wide * scale).astype(jnp.float8_e4m3fn)
                .astype(jnp.float32) / scale).astype(a.dtype)

    rounded = jax.jit(lambda p: jax.tree.map(through_fp8, p))(params)
    low = InferenceEngineV2(engine.model, params=rounded,
                            config=engine.config)
    yield "fp8_weights", measure(low, params)


def main(argv=None, root: str = mf.CHECKOUT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--prompt-tokens", type=int, default=200)
    ap.add_argument("--variants", default=",".join(VARIANTS),
                    help="which builds to measure, of " + ", ".join(VARIANTS))
    args = ap.parse_args(argv)
    wanted = args.variants.split(",")
    if not wanted or set(wanted) - set(VARIANTS):
        ap.error(f"--variants: a list of {', '.join(VARIANTS)}")
    manifest = mf.load(root)
    cell = next(w["name"] for w in manifest["workloads"]
                if w["config"] == args.config)
    info = mf.resolve(manifest, cell, root)

    import jax

    block = info["config"]["engine"]["kv_block_size"]
    steps = info["config"]["check"]["decode_steps"]
    vocab = info["config"]["transformer_config"]["vocab_size"]
    prompt = np.random.default_rng([args.seed, 0x746f]).integers(
        0, vocab, size=args.prompt_tokens).tolist()
    kv_blocks = -(-(args.prompt_tokens + steps) // block) + 1
    for name, record in variants(info, args.seed, prompt, kv_blocks, wanted):
        print(json.dumps({
            "config": args.config, "variant": name,
            "platform": jax.devices()[0].platform,
            "prompt_tokens": args.prompt_tokens, "seed": args.seed,
            "max_rel_err": record.get("max_rel_err"),
            "rms_rel_err": record.get("rms_rel_err"),
            "tolerance": record.get("tolerance"),
            "rms_tolerance": record.get("rms_tolerance"),
            "within": record["ok"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
