"""The faults a configuration's serving check must catch, planted where
the check can see them, for a configuration whose weights fit the device
once and not twice (``tolerance.py``'s ``fp8_weights`` builds a second
engine beside the first):

    python3 -m benchmark.controls --config <name> [--seed 1]
                                  [--prompt-tokens 4217]
                                  [--rows-out <file>]

Each control is the cell's own check — ``serve_runner.check_logits``, the
configuration's block replay against the block's reference, at the
file's tolerances — on one seeded prompt, and says what the check has to
answer:

``served``        the engine as the cell builds it: ``ok``.
``lost_block``    one control a K/V layer group (``lost_block_g0``, ...):
                  before a sequence's first one-token step, one live
                  block of its table in that group is made to point at
                  its neighbour's — what a block handed back too early
                  and given out again reads as. Not ``ok``, for every
                  group, or the layers of that group are outside
                  ``correct``.
``fp8_weights``   every weight rounded through float8_e4m3 under a scale
                  a tensor, *in place*: the served engine is let go, the
                  leaves are rounded one at a time, an engine is built on
                  them and replays the prompt; then it is let go too, the
                  weights are drawn again from the seed, and the check
                  compares what was read with their reference. Not
                  ``ok``.

One JSON line a control, ``as_expected`` in each; exit code 0 when every
one is. ``--rows-out`` keeps every compared row — its disagreement and,
where the block has ``tie_margins``, its routing margins with every
position answered — for setting a tolerance or a tie margin from. It runs
wherever JAX runs; a disagreement is no device metric, say where it was
read.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import types

import numpy as np

from . import arithmetic as ar
from . import manifest as mf
from . import serve_runner as sr


class LostBlock:
    """The engine, with one live block of ``group`` lost to every sequence
    at its first one-token put: the entry in the middle of its live blocks
    points at the next one's block from then on."""

    def __init__(self, engine, group: int):
        self._engine, self._group, self._lost = engine, group, set()

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def put(self, uids, tokens):
        sm = self._engine.state_manager
        for uid, new in zip(uids, tokens):
            seq = sm.get_sequence(uid)
            if len(new) != 1 or seq is None or uid in self._lost:
                continue
            rows = sm.table_rows(seq)
            first = seq.released[self._group]
            at = (first + rows.shape[1]) // 2
            if at + 1 < rows.shape[1]:
                seq.rows[self._group, at] = seq.rows[self._group, at + 1]
                self._lost.add(uid)
        return self._engine.put(uids, tokens)


def through_fp8_in_place(flat: list, tree):
    """Every leaf of ``flat`` (a flattened tree's, the only references to
    them) through float8_e4m3 and back under a scale of its own, one leaf
    at a time and in two programs with the float8 array between them (in
    one program XLA drops the round trip); ``flat`` is emptied as it goes,
    so one copy is resident. Returns the tree."""
    import jax
    import jax.numpy as jnp

    down = jax.jit(lambda a, s: (a.astype(jnp.float32) * s
                                 ).astype(jnp.float8_e4m3fn))
    up = jax.jit(lambda f, s, like: (f.astype(jnp.float32) / s
                                     ).astype(like.dtype))
    out = []
    while flat:
        a = flat.pop(0)
        scale = 448.0 / jnp.max(jnp.abs(a.astype(jnp.float32)))
        like = jnp.zeros((), a.dtype)
        low = down(a, scale)
        del a
        low.block_until_ready()
        out.append(up(low, scale, like))
    return jax.tree_util.tree_unflatten(tree, out)


def measure(info: dict, name: str, engine, params, prompt, rows_out=None,
            views=None) -> dict:
    """The harness's comparison of one prompt: ``check_logits`` over the
    block's replay through ``engine`` — or over ``views`` read earlier, by
    an engine that is gone by now — at the file's tolerances."""
    block, check = info["block"], info["config"]["check"]
    replay = getattr(block, "replay", sr.causal_replay)
    kept = []

    def keeping(*args):
        kept.extend(views if views is not None else replay(*args))
        return kept

    shim = types.SimpleNamespace(logits=block.logits, replay=keeping)
    record = sr.check_logits(
        engine, params, dict(info, block=shim), [prompt],
        check["decode_steps"], check["tolerance"], check["rms_tolerance"])
    if rows_out is not None:
        _dump_rows(rows_out, name, kept, params,
                   info["config"]["transformer_config"], block)
    return record


def run(info: dict, seed: int, prompt, rows_out=None):
    """(control, expected ok, ``check_logits`` record) for each control."""
    import jax

    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2

    from .model import seeded_params

    cfg, params, engine = sr.build(info, seed)
    yield "served", True, measure(info, "served", engine, params, prompt,
                                  rows_out)
    for g in range(len(engine.state_manager.groups)):
        name = f"lost_block_g{g}"
        yield name, False, measure(info, name, LostBlock(engine, g), params,
                                   prompt, rows_out)
    model, sizing = engine.model, engine.config
    del engine
    gc.collect()
    flat, tree = jax.tree_util.tree_flatten(params)
    del params
    rounded = through_fp8_in_place(flat, tree)
    low = InferenceEngineV2(model, params=rounded, config=sizing)
    uid = sr._OWN_UID + (1 << 20)
    views = getattr(info["block"], "replay", sr.causal_replay)(
        low, uid, list(prompt), info["config"]["check"]["decode_steps"])
    low.flush(uid)
    del low, rounded
    gc.collect()
    params = seeded_params(model, seed, cfg.dtype)
    gone = types.SimpleNamespace(flush=lambda uid: None)
    yield "fp8_weights", False, measure(info, "fp8_weights", gone, params,
                                        prompt, rows_out, views)


def _dump_rows(path, name, views, params, arch, block):
    """One line a compared row: how far the engine's logits lie from the
    reference's with every position answered, and the margins."""
    import jax

    margins_of = getattr(block, "tie_margins", None)
    ref = jax.jit(lambda p, t: margins_of(p, t, arch) if margins_of
                  else (block.logits(p, t, arch), None))
    with open(path, "a") as out:
        for tokens, rows, got in views:
            padded = np.zeros((-(-len(tokens) // 256) * 256,), np.int32)
            padded[:len(tokens)] = tokens
            want, margins = ref(params, padded)
            want = np.asarray(want)
            margins = None if margins is None else np.asarray(margins)
            for row, g in zip(rows, got):
                out.write(json.dumps({
                    "control": name, "row": int(row),
                    "max_rel_err": float(ar.max_rel_err(g, want[row])),
                    "rms_rel_err": float(ar.rms_rel_err(g, want[row])),
                    "margins": None if margins is None else
                    [float(m) for m in margins[:, row]]}) + "\n")


def main(argv=None, root: str = mf.CHECKOUT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--prompt-tokens", type=int, default=None,
                    help="the check's min_prompt_tokens + 120 where it "
                    "names one, else 200")
    ap.add_argument("--rows-out", default=None)
    args = ap.parse_args(argv)
    manifest = mf.load(root)
    cell = next(w["name"] for w in manifest["workloads"]
                if w["config"] == args.config)
    info = mf.resolve(manifest, cell, root)

    import jax

    from . import device

    device.enable_compile_cache()
    check = info["config"]["check"]
    n = args.prompt_tokens or (check["min_prompt_tokens"] + 120
                               if check.get("min_prompt_tokens") else 200)
    vocab = info["config"]["transformer_config"]["vocab_size"]
    prompt = np.random.default_rng([args.seed, 0x6374]).integers(
        0, vocab, size=n).tolist()
    as_expected = True
    for name, expected, record in run(info, args.seed, prompt,
                                      args.rows_out):
        as_expected &= record["ok"] == expected
        print(json.dumps({
            "config": args.config, "control": name,
            "platform": jax.devices()[0].platform,
            "prompt_tokens": n, "seed": args.seed,
            "ok": record["ok"], "expected_ok": expected,
            "as_expected": record["ok"] == expected,
            "max_rel_err": record.get("max_rel_err"),
            "rms_rel_err": record.get("rms_rel_err"),
            "compared": record.get("compared"),
            "unanswered": record.get("unanswered"),
            "tolerance": record["tolerance"],
            "rms_tolerance": record["rms_tolerance"],
            "why": record.get("why")}), flush=True)
    return 0 if as_expected else 1


if __name__ == "__main__":
    sys.exit(main())
