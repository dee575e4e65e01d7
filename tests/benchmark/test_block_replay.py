"""What a block module may say about how its kind of model generates, and
what the harness does without it (CPU, tiny sizes):

- a test double whose forward yields a block of tokens
  (``twins/blocks/wide_rows.py``: ``replay``, ``warm_up``, ``qk_pairs``),
  added to a temporary checkout as files and found by name by ``run.py``,
  ``tolerance.py`` and ``sweep.py``;
- the default replay against the loop ``check_logits`` held until PR 49,
  kept here as the oracle, number for number, over three tiny twins;
- what is not a number on either side of the comparison, the least prompt
  length a configuration can ask for, and a sequence left behind."""

import itertools
import json
import os
import shutil

import numpy as np
import pytest
from qwen3_next_tiny import TINY_QWEN3_NEXT
from test_benchmark_runners import (TINY_NEOX, TRAFFIC,  # noqa: F401
                                    _harness_hashes, _read, _write, checkout,
                                    rehearse)

from benchmark import arithmetic as ar
from benchmark import manifest as mf
from benchmark import serve_runner as sr

TWINS = os.path.join(os.path.dirname(__file__), "twins")
WIDE = dict(TINY_NEOX, block="wide_rows")
W, STEPS, REQUESTS = 4, WIDE["check"]["decode_steps"], \
    WIDE["check"]["requests"]
#: a prompt's views: its last row, then ``W - 1`` rolled-back forwards and
#: the committing one a step; and the rows they read
VIEWS = 1 + STEPS * W
ROWS = 1 + STEPS * ((W - 1) * W + 1)


# ------------------------------------------ the double, added as files

def add_wide_rows(root, traffic_name="few", mix=None):
    """A later PR's files and entries: the block module, a configuration
    that names it, a mix, a cell."""
    shutil.copy(os.path.join(TWINS, "blocks", "wide_rows.py"),
                os.path.join(root, "benchmark/blocks/wide_rows.py"))
    _write(os.path.join(root, "benchmark/configs/tiny-wide.json"), WIDE)
    _write(os.path.join(root, f"benchmark/traffic/{traffic_name}.json"),
           mix or dict(TRAFFIC["batch"], schedule_seed=2))
    cell = "tiny-wide." + traffic_name
    _write(os.path.join(root, f"benchmark/workloads/{cell}.json"),
           {"runner": "serve", "rate_rps": 10.0})
    manifest = mf.load(root)
    manifest["configs"].append(
        {"name": "tiny-wide", "source": "a later PR's",
         "file": "benchmark/configs/tiny-wide.json", "reduced": [],
         "why": "a forward that yields a block of tokens"})
    manifest["workloads"].append(
        {"name": cell, "config": "tiny-wide", "traffic": traffic_name,
         "chips": 1, "why": "a later PR's cell"})
    like = "mistral-7b.batch" if mix is None else "pythia-1.4b.chat"
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if like in m.get("workloads", []):
            m["workloads"].append(cell)
    _write(os.path.join(root, "BENCHMARK.json"), manifest)
    mf.validate(manifest, root)
    return cell


def test_a_replay_and_a_warm_up_added_as_files_are_used_by_the_run(
        checkout, capsys):  # noqa: F811
    before = _harness_hashes(checkout)
    cell = add_wide_rows(checkout)
    line, extra = rehearse(checkout, capsys, cell, 0)
    assert line["correct"], extra["why_not"]
    assert line["failed"] == 0
    counters = extra["counters"]
    check = counters["logits_check"]
    assert check["sampled"] == REQUESTS and check["steps_each"] == STEPS + 1
    assert check["views"] == REQUESTS * VIEWS
    assert check["compared"] == REQUESTS * ROWS and check["unanswered"] == 0
    assert 0 < check["max_rel_err"] < 1e-4 and check["rms_rel_err"] < 1e-4
    # the grid's 3 x 6 + 4 puts, then the block's one
    assert counters["warm_up_calls"] == 3 * 6 + 4 + 1
    assert counters["compiles_in_window"] == 0
    assert counters["state_slots_held"] == 0
    assert line["checks"]["kv_blocks_missing"] == {"value": 0, "limit": 0}
    assert line["checks"]["logits_max_rel_err"] == {
        "value": check["max_rel_err"], "limit": 1e-4}
    assert _harness_hashes(checkout).items() >= before.items()


def test_the_tolerance_tool_follows_the_replay_and_skips_a_build(
        checkout, capsys):  # noqa: F811
    from benchmark import tolerance

    add_wide_rows(checkout)
    seen = []
    real = sr.check_logits

    def spy(*args):
        seen.append(real(*args))
        return seen[-1]

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(sr, "check_logits", spy)
        capsys.readouterr()
        assert tolerance.main(
            ["--config", "tiny-wide", "--prompt-tokens", "40", "--variants",
             "fp8_weights,float32"], root=checkout) == 0
    lines = [json.loads(x) for x in
             capsys.readouterr().out.strip().splitlines()]
    # in the tool's own order, whatever order they were asked in
    assert [x["variant"] for x in lines] == ["float32", "fp8_weights"]
    assert [x["within"] for x in lines] == [True, False]
    assert [r["views"] for r in seen] == [VIEWS, VIEWS]
    with pytest.raises(SystemExit):
        tolerance.main(["--config", "tiny-wide", "--variants", "float16"],
                       root=checkout)


def test_the_sweep_warms_up_the_blocks_programs(checkout, capsys,  # noqa: F811
                                                monkeypatch):
    from benchmark import device as dev
    from benchmark import sweep

    cell = add_wide_rows(checkout, "paced", dict(TRAFFIC["chat"],
                                                 schedule_seed=4))
    load, resolve, warm_up = mf.load, mf.resolve, sr.warm_up
    calls = []
    monkeypatch.setattr(mf, "load", lambda: load(checkout))
    monkeypatch.setattr(mf, "resolve",
                        lambda m, name: resolve(m, name, checkout))
    monkeypatch.setattr(dev, "require_chips", lambda n: {"kind": "cpu"})
    monkeypatch.setattr(sr, "warm_up", lambda engine, block: calls.append(
        (block.__name__, warm_up(engine, block))))
    capsys.readouterr()
    assert sweep.main(["--workload", cell, "--rates", "10",
                       "--seconds", "1.5"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert calls == [("benchmark.blocks.wide_rows", 3 * 6 + 4 + 1)]
    assert out["compiles_in_window"] == 0 and out["due_in_window"] >= 8


# ------------------------------------- the double, on an engine of its own

class Wide:
    """The double over the Pythia twin: one engine, warmed up once."""

    def __init__(self):
        from benchmark import device as dev

        self.block = mf.find_module(TWINS, "blocks", "wide_rows")
        self.info = {"config": WIDE, "block": self.block}
        _, self.params, self.engine = sr.build(self.info, 7)
        self.watch = dev.CompileWatch()
        self.calls = sr.warm_up(self.engine, self.block)
        self.prompts = [np.random.default_rng(n).integers(
            0, 256, size=n).tolist() for n in (70, 45)]

    def check(self, engine=None):
        engine = engine or self.engine
        record = sr.check_logits(engine, self.params, self.info,
                                 self.prompts, STEPS, 1e-4, 1e-4)
        assert engine.state_manager.available_blocks == \
            WIDE["engine"]["kv_blocks"]
        return record


@pytest.fixture(scope="module")
def wide():
    return Wide()


def test_nothing_compiles_behind_the_warm_up_and_what_it_forgot_is_seen(
        wide, monkeypatch):
    assert wide.calls == 3 * 6 + 4 + 1
    uids = itertools.count(sr._OWN_UID + (1 << 21))

    def replay():
        before, uid = wide.watch.count, next(uids)
        views = wide.block.replay(wide.engine, uid, wide.prompts[0], STEPS)
        wide.engine.flush(uid)
        return views, wide.watch.count - before

    views, compiled = replay()
    assert compiled == 0 and len(views) == VIEWS
    assert sorted(len(rows) for _, rows, _ in views) == \
        [1] * (1 + STEPS) + [W] * (STEPS * (W - 1))
    assert all(np.asarray(got).shape == (len(rows), 256)
               for _, rows, got in views)
    assert wide.engine.state_manager.available_blocks == \
        WIDE["engine"]["kv_blocks"]
    # a width the hook did not run is a program of its own: the watch
    # counts it, and in a window that is ``correct: false``
    monkeypatch.setattr(wide.block, "W", 2)
    assert replay()[1] > 0


def test_the_check_holds_every_view_to_the_reference(wide):
    record = wide.check()
    assert record["ok"], record["why"]
    assert record["views"] == 2 * VIEWS and record["compared"] == 2 * ROWS
    assert 0 < record["max_rel_err"] < 1e-4 and record["rms_rel_err"] < 1e-4


@pytest.mark.parametrize("which", [1, VIEWS - 1])
def test_a_view_whose_rows_are_off_by_one_fails(wide, monkeypatch, which):
    """Row p of the reference is not row p + 1: one view of one prompt
    that names its rows wrongly is enough."""
    real = wide.block.replay

    def shifted(*args):
        views = real(*args)
        tokens, rows, got = views[which]
        views[which] = (tokens, [r - 1 for r in rows], got)
        return views

    monkeypatch.setattr(wide.block, "replay", shifted)
    record = wide.check()
    # (neighbouring rows of a tiny random model differ by little: the
    # greedy token repeats; fifty tolerances are a fault all the same)
    assert not record["ok"] and record["max_rel_err"] > 5e-3
    assert "engine vs reference" in record["why"]


def test_an_engine_with_spoiled_weights_fails(wide):
    import jax

    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2

    wrong = jax.tree_util.tree_map_with_path(
        lambda path, a: a * 0.5 if "lm_head" in str(path) else a,
        wide.params)
    spoiled = InferenceEngineV2(wide.engine.model, params=wrong,
                                config=wide.engine.config)
    record = wide.check(spoiled)
    assert not record["ok"] and record["rms_rel_err"] > 0.1


def test_the_probe_counts_pairs_as_the_block_does(wide):
    """``qk_pairs`` of the forward spans is the block's count where it
    gives one (the attention rooflines divide by it)."""
    from benchmark.probe import Probe

    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2

    seen = {}
    for name, count in (("causal", None), ("block", wide.block.qk_pairs)):
        engine = InferenceEngineV2(wide.engine.model, params=wide.params,
                                   config=wide.engine.config)
        probe = Probe()
        sr._instrument_engine(probe, engine, *([count] if count else []))
        engine.put([1, 2], [[5] * 8, [6] * 4])
        engine.put([1], [[7] * 4])
        for uid in (1, 2):
            engine.flush(uid)
        seen[name] = [a["qk_pairs"] for n, _, _, a in probe.spans
                      if n == "forward"]
    assert seen["causal"] == [8 * 9 // 2 + 4 * 5 // 2, 4 * 8 + 4 * 5 // 2]
    assert seen["block"] == [8 * 8 + 4 * 4, 4 * 12]


# ------------------------------------ the default replay and the old loop

def old_check_logits(engine, params, info, sample, decode_steps, tolerance,
                     rms_tolerance):
    """``serve_runner.check_logits`` as it stood until PR 49, verbatim:
    the oracle the default replay is held to."""
    import jax

    block, arch = info["block"], info["config"]["transformer_config"]
    chunk = engine.config.max_chunk_tokens
    width = -(-max(len(p) + decode_steps for p in sample) // 256) * 256
    ref_fn = jax.jit(lambda p, t: block.logits(p, t, arch))
    worst = worst_rms = 0.0
    for i, prompt in enumerate(sample):
        uid = sr._OWN_UID + (1 << 20) + i
        got, tokens = [], list(prompt)
        for at in range(0, len(prompt), chunk):
            lg = engine.put([uid], [prompt[at:at + chunk]])
        got.append(np.asarray(lg[0], np.float32))
        for _ in range(decode_steps):
            tokens.append(int(np.argmax(got[-1])))
            got.append(np.asarray(engine.put([uid], [[tokens[-1]]])[0],
                                  np.float32))
        engine.flush(uid)
        padded = np.zeros((width,), np.int32)
        padded[:len(tokens)] = tokens
        want = np.asarray(ref_fn(params, padded))
        for step, g in enumerate(got):
            w = want[len(prompt) - 1 + step]
            if not np.isfinite(g).all():
                return {"ok": False, "why": f"sample {i}: logits not finite"}
            worst = max(worst, ar.max_rel_err(g, w))
            worst_rms = max(worst_rms, ar.rms_rel_err(g, w))
    ok = worst <= tolerance and worst_rms <= rms_tolerance
    return {"ok": ok, "max_rel_err": worst, "tolerance": tolerance,
            "rms_rel_err": worst_rms, "rms_tolerance": rms_tolerance,
            "sampled": len(sample), "steps_each": decode_steps + 1,
            "why": None if ok else
            f"engine vs reference logits: max {worst:.4f} of range "
            f"(<= {tolerance}), rms {worst_rms:.4f} (<= {rms_tolerance})"}


def _twin(name):
    if name == "dense":
        return TINY_NEOX
    if name == "qwen3-next-80b-a3b":
        return TINY_QWEN3_NEXT
    return _read(os.path.join(TWINS, "configs", name + ".json"))


@pytest.mark.parametrize("name", ["dense", "qwen3-next-80b-a3b",
                                  "dots3-note-prev"])
@pytest.mark.parametrize("tolerance", [1e-4, 1e-9], ids=["held", "failed"])
def test_the_default_replay_reads_what_the_old_loop_read(name, tolerance):
    """A block with no ``replay`` of its own: the same engine calls in the
    same order, one view and one reference forward a prompt, and the old
    record's every number (one attention-and-MLP model, one with state
    slots, one with two layer groups; a tolerance that holds and one that
    cannot, for the sentence that says why)."""
    config = dict(_twin(name))
    config["engine"] = dict(config["engine"], compile_ahead=0)
    info = {"config": config,
            "block": mf.find_module(mf.HERE, "blocks", config["block"])}
    assert not hasattr(info["block"], "replay")
    _, params, engine = _engine_of(name, info)
    vocab = config["transformer_config"]["vocab_size"]
    sample = [np.random.default_rng([9, n]).integers(0, vocab, size=n)
              .tolist() for n in (70, 45)]
    old = old_check_logits(engine, params, info, sample, 2, tolerance, 1e-4)
    new = sr.check_logits(engine, params, info, sample, 2, tolerance, 1e-4)
    assert {k: new[k] for k in old} == old
    assert old["ok"] == (tolerance == 1e-4) and old["max_rel_err"] > 0
    assert new["views"] == 2
    assert new["compared"] + new["unanswered"] == 2 * 3
    occupancy = engine.occupancy()
    assert occupancy["state_slots_used"] == 0
    assert engine.state_manager.available_blocks == \
        config["engine"]["kv_blocks"]


_ENGINES = {}


def _engine_of(name, info):
    """One engine a twin for the file's run (the second tolerance finds
    every program compiled)."""
    if name not in _ENGINES:
        _ENGINES[name] = sr.build(info, 5)
    return _ENGINES[name]


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_default_pair_count_is_the_causal_one(seed):
    rng = np.random.default_rng(seed)
    new = rng.integers(1, 2049, size=32).astype(np.int64)
    seen = rng.integers(0, 100_000, size=32).astype(np.int64)
    assert sr.causal_qk_pairs(new, seen) == \
        int((new * seen + new * (new + 1) // 2).sum())
    # by hand: 3 new behind 10 seen see 11 + 12 + 13 keys
    assert sr.causal_qk_pairs(np.asarray([3]), np.asarray([10])) == 36


# ----------------------------------------- what is not a number, by a mask

class _TableEngine:
    """A stand-in engine whose logits are a row of a table by the token
    at that position (no context), ``spoil`` applied to one put's rows."""

    class config:
        max_chunk_tokens = 8

    def __init__(self, table, spoil_put=None, value=np.nan):
        self.table, self.spoil_put, self.value = table, spoil_put, value
        self.puts, self.flushed = 0, []

    def put(self, uids, tokens_list):
        rows = np.stack([self.table[t[-1]] for t in tokens_list])
        if self.puts == self.spoil_put:
            rows[:, 3] = self.value
        self.puts += 1
        return rows

    def flush(self, uid):
        self.flushed.append(uid)


def _table_block(table, blank=(), poke=None):
    """A reference that answers from the same table; no answer (a row of
    NaN) at the positions in ``blank``, one inf at ``poke``."""
    import jax.numpy as jnp

    class block:
        @staticmethod
        def logits(params, tokens, arch):
            out = jnp.asarray(table)[tokens]
            for at in blank:
                out = out.at[at].set(jnp.nan)
            if poke is not None:
                out = out.at[poke, 0].set(jnp.inf)
            return out

    return {"block": block, "config": {"transformer_config": {}}}


@pytest.mark.parametrize("case, ok, why, counts", [
    ("sound", True, None, (3, 0)),
    ("engine-nan", False, "sample 0: logits not finite", None),
    ("engine-inf", False, "sample 0: logits not finite", None),
    ("reference-row-blank", True, None, (2, 1)),
    ("reference-inf", False, "the reference's logits at 12 are not", None),
    ("reference-all-blank", True, None, (0, 3)),
])
def test_a_value_that_is_not_a_number_is_masked_or_fails(case, ok, why,
                                                        counts):
    """A NaN is never folded with ``max``: the engine's fails, a reference
    row that is NaN throughout is the block's own mask (counted; a check
    that compared nothing says so and passes, as it did), and any other
    non-finite value of the reference fails."""
    table = np.random.default_rng(3).normal(size=(16, 6)).astype(np.float32)
    prompt = list(range(12))        # two chunks of 8; rows 11, 12, 13 read
    engine = _TableEngine(table, **{
        "engine-nan": dict(spoil_put=2), "engine-inf": dict(
            spoil_put=3, value=np.inf)}.get(case, {}))
    info = _table_block(table, **{
        "reference-row-blank": dict(blank=(12,)),
        "reference-inf": dict(poke=12),
        "reference-all-blank": dict(blank=(11, 12, 13))}.get(case, {}))
    record = sr.check_logits(engine, None, info, [prompt], 2, 1e-6, 1e-6)
    assert record["ok"] is ok
    assert (record["why"] is None) if why is None else (why in record["why"])
    if counts is not None:
        assert (record["compared"], record["unanswered"]) == counts
        assert record["max_rel_err"] == 0.0
    assert engine.puts == 4 and len(engine.flushed) == 1


# ------------------------------------------------ the sample and the slots

class _Done:
    def __init__(self, n, ok=True):
        self.ok, self.req = ok, type("Req", (), {"prompt": list(range(n))})


def test_a_configuration_can_hold_its_checked_prompts_past_a_length():
    records = [_Done(n) for n in (10, 40, 50, 60, 90, 300)] + \
        [_Done(55, ok=False)]
    check = {"requests": 3, "max_prompt_tokens": 100}
    lengths = lambda c, seed: sorted(
        len(p) for p in sr.checked_sample(records, c, seed))
    # no least length named: as it was, a draw of the finished ones
    assert {n for seed in range(20) for n in lengths(check, seed)} == \
        {10, 40, 50, 60, 90}
    assert all(len(lengths(check, seed)) == 3 for seed in range(20))
    # the same draw as PR 48's inline code made
    picks = np.random.default_rng([7, 0x636b]).choice(5, size=3,
                                                      replace=False)
    assert sr.checked_sample(records, check, 7) == \
        [records[i].req.prompt for i in picks]
    held = dict(check, min_prompt_tokens=50)
    assert all(lengths(held, seed) == [50, 60, 90] for seed in range(5))
    assert sr.checked_sample(records, dict(held, min_prompt_tokens=200),
                             1) == []


def test_a_sequence_left_behind_fails_the_run(checkout, capsys,  # noqa: F811
                                              monkeypatch):
    """A replay that leaves a sequence in the engine: its KV blocks and,
    in a model with recurrent layers, its state slot are missing after
    the check, and the run says both."""
    cell = "qwen3-next-80b-a3b.longdoc"
    path = os.path.join(checkout, "benchmark/workloads", cell + ".json")
    _write(path, dict(_read(path), rate_rps=10.0))
    real = sr.causal_replay

    def leaky(engine, uid, prompt, decode_steps):
        engine.put([uid + (1 << 30)], [prompt[:3]])
        return real(engine, uid, prompt, decode_steps)

    monkeypatch.setattr(sr, "causal_replay", leaky)
    line, extra = rehearse(checkout, capsys, cell, 0)
    assert not line["correct"]
    assert extra["why_not"] == ["KV blocks were not all returned",
                                "2 state slots were not returned"]
    assert extra["counters"]["logits_check"]["ok"]
    assert extra["counters"]["state_slots_held"] == 2
    assert line["checks"]["state_slots_held"] == {"value": 2, "limit": 0}
    assert line["checks"]["kv_blocks_missing"]["value"] == 2
