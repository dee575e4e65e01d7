"""Runner for cells that serve requests: ``ServingFrontend`` over one
``InferenceEngineV2`` on one chip, driven only through
``ServingFrontend.submit`` and the handles it returns.

Open loop: requests are submitted from this thread at their due times, at
the rate fixed in the cell's file; latency runs from the due time. Closed
loop: ``clients`` callers, each sends its next request when its last has
completed. The window starts after ``preroll_s`` of traffic; after it the
run waits at most ``drain_s`` for what is still in flight, and what has
not finished then is ``failed``.
"""

from __future__ import annotations

import itertools
import time

import numpy as np

from . import arithmetic as ar
from . import traffic
from .device import TraceWindow, memory_peak_bytes
from .model import seeded_params, transformer_config
from .probe import Probe

#: uids the benchmark's own engine calls use (warm-up, logits replay);
#: the frontend's start at 1
_OWN_UID = 1 << 40
_POLL_S = 0.001


# ------------------------------------------------------------------ build

def build(info: dict, seed: int, arch_overrides=None):
    """Model, seeded weights on the device, and the engine, sized as the
    configuration's file says."""
    from deepspeed_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2, RaggedInferenceEngineConfig)
    from deepspeed_tpu.models.transformer import CausalLM

    cfg = transformer_config(info, **(arch_overrides or {}))
    model = CausalLM(cfg)
    params = seeded_params(model, seed, cfg.dtype)
    sizing = {k: v for k, v in info["config"]["engine"].items()
              if not k.startswith("_")}
    engine = InferenceEngineV2(model, params=params,
                               config=RaggedInferenceEngineConfig(**sizing))
    return cfg, params, engine


def bucket_grid(engine):
    """Every [sequences, chunk] shape the engine's forward can be asked
    for: powers of two up to its caps."""
    def pow2(cap):
        out, b = [], 1
        while b < cap:
            out.append(b)
            b *= 2
        return out + [cap]

    return [(s, c) for s in pow2(engine.config.max_ragged_sequence_count)
            for c in pow2(engine.config.max_chunk_tokens)]


def warm_up(engine, block=None) -> int:
    """Run every shape the traffic can reach through ``engine.put``, so
    that nothing compiles in the window: each [S, C] bucket once, each
    sequence count 1..max once, then what the cell's block type adds
    (below). KV blocks go back at once. Returns the ``put`` calls made."""
    uid = itertools.count(_OWN_UID)
    calls = 0

    def put(n_seqs, chunk):
        nonlocal calls
        uids = [next(uid) for _ in range(n_seqs)]
        np.asarray(engine.put(uids, [[0] * chunk] + [[0]] * (n_seqs - 1)))
        for u in uids:
            engine.flush(u)
        calls += 1

    for s, c in bucket_grid(engine):
        put(s, c)
    for n in range(1, engine.config.max_ragged_sequence_count + 1):
        put(n, 1)
    # a block type whose served decode reaches programs the grid does not
    # (a forward that reads several rows, ``verify_width``, is a program
    # of its own a width) runs them here: ``warm_up(engine, uids)`` of
    # ``blocks/<block>.py``, fresh uids in, its ``put`` calls out. It is
    # called from this frame, behind the grid, so the grid's programs keep
    # the Python stack the compile cache keyed them under; the
    # ``CompileWatch`` judges a hook that forgets a program as any run
    own = getattr(block, "warm_up", None)
    return calls + (int(own(engine, uid)) if own is not None else 0)


# ---------------------------------------------------------------- traffic

class _Record:
    __slots__ = ("req", "due", "sent", "handle", "times", "reason")

    def __init__(self, req, due, sent, handle):
        self.req, self.due, self.sent, self.handle = req, due, sent, handle
        self.times, self.reason = [], None

    def collect(self):
        self.times += [ev.t for ev in self.handle.drain()]
        self.reason = self.handle.finish_reason

    @property
    def ok(self):
        return (self.reason == "length"
                and len(self.times) == self.req.new_tokens)


def _finished(handle) -> bool:
    return handle.finish_reason is not None


def open_loop(fe, stream, t0: float, t_stop: float):
    """Submit each request at ``t0 + due_s`` until ``t_stop``."""
    records = []
    for req in stream:
        due = t0 + req.due_s
        if due >= t_stop:
            break
        delay = due - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        sent = time.monotonic()
        records.append(_Record(req, due, sent,
                               fe.submit(req.prompt,
                                         max_new_tokens=req.new_tokens)))
    return records


def closed_loop(fe, stream, clients: int, t_stop: float):
    """``clients`` callers; each sends the stream's next request as soon
    as its last one is done. One polling thread stands for all of them."""
    records, active = [], []

    def send():
        req = next(stream)
        now = time.monotonic()
        rec = _Record(req, now, now,
                      fe.submit(req.prompt, max_new_tokens=req.new_tokens))
        records.append(rec)
        return rec

    active = [send() for _ in range(clients)]
    while time.monotonic() < t_stop:
        for i, rec in enumerate(active):
            if _finished(rec.handle):
                active[i] = send()
        time.sleep(_POLL_S)
    return records


def drain(fe, records, drain_s: float):
    """Wait up to ``drain_s`` for what is in flight, then cancel the rest
    (so their KV blocks come back) and read every stream."""
    deadline = time.monotonic() + drain_s
    pending = [r for r in records if not _finished(r.handle)]
    while pending and time.monotonic() < deadline:
        time.sleep(0.01)
        pending = [r for r in pending if not _finished(r.handle)]
    for r in pending:
        r.handle.cancel()
    fe.wait_all([r.handle for r in pending], timeout=10.0)
    for r in records:
        r.collect()
    return len(pending)


# ------------------------------------------------------------ correctness

def causal_replay(engine, uid, prompt, decode_steps: int):
    """How a causal model generates, and the replay of a block type that
    names none of its own: the prompt in chunks of ``max_chunk_tokens``,
    then ``decode_steps`` steps, a step being one greedy token put back
    alone, each reading the logits of the sequence's last position. One
    view: the tokens as they stand at the end (a causal row sees nothing
    behind it, so every earlier forward saw them so), and the rows
    ``len(prompt) - 1 ... + decode_steps``."""
    chunk = engine.config.max_chunk_tokens
    got, tokens = [], list(prompt)
    for at in range(0, len(prompt), chunk):
        lg = engine.put([uid], [prompt[at:at + chunk]])
    got.append(np.asarray(lg[0], np.float32))
    for _ in range(decode_steps):
        tokens.append(int(np.argmax(got[-1])))
        got.append(np.asarray(engine.put([uid], [[tokens[-1]]])[0],
                              np.float32))
    first = len(prompt) - 1
    return [(tokens, range(first, first + len(got)), got)]


def check_logits(engine, params, info, sample, decode_steps: int,
                 tolerance: float, rms_tolerance: float) -> dict:
    """A seeded sample of requests, each run through the engine the way
    its block type generates (``replay`` of ``blocks/<block>.py``, else
    ``causal_replay``), and every logits row the engine gave held against
    the configuration's block reference (``info["block"].logits``).

    A replay returns **views**, one a forward whose logits it read:
    ``(tokens, rows, got)`` — the whole token list as the model saw it at
    that forward (placeholders such as mask tokens included), the
    positions whose logits were read, and the engine's float32 logits for
    them, ``[len(rows), vocab]``. The harness pads each view's tokens on
    the right with zeros to one width, runs the reference once over them,
    and compares ``want[rows]`` with ``got`` row by row: the largest
    disagreement relative to the range, and the RMS relative to the RMS (a
    lower precision shows there first); the worst of either is held to the
    configuration's tolerances. The uid is the harness's and so is the
    ``flush``; what a replay leaves behind fails ``blocks_back``.

    What is not a number is never folded: a non-finite value in the
    engine's logits fails the check. A reference row that is NaN
    throughout is the block saying that it gives no answer there (a
    routing decision within rounding of its edge: ``blocks/trinity.py``):
    the position is masked out and counted (``unanswered``); any other
    non-finite value of the reference fails. A check whose every row went
    unanswered passes, as it always did, and says ``compared: 0``: with
    12 rows a run and up to four in ten unanswered that is one run in some
    tens of thousands, and a later PR whose change is sound should not be
    refused for it; that a block's reference answers at all is held by
    its rehearsal (``0 < max_rel_err``)."""
    import jax

    block, arch = info["block"], info["config"]["transformer_config"]
    replay = getattr(block, "replay", causal_replay)
    views = []
    for i, prompt in enumerate(sample):
        uid = _OWN_UID + (1 << 20) + i
        views += [(i, *view) for view in
                  replay(engine, uid, list(prompt), decode_steps)]
        engine.flush(uid)
    record = {"tolerance": tolerance, "rms_tolerance": rms_tolerance,
              "sampled": len(sample), "steps_each": decode_steps + 1,
              "views": len(views)}
    if not views:
        return dict(record, ok=False, why="the replay read no logits")
    width = -(-max(len(tokens) for _, tokens, _, _ in views) // 256) * 256
    ref_fn = jax.jit(lambda p, t: block.logits(p, t, arch))
    worst = worst_rms = 0.0
    compared = unanswered = 0
    for i, tokens, rows, got in views:
        padded = np.zeros((width,), np.int32)
        padded[:len(tokens)] = tokens
        want = np.asarray(ref_fn(params, padded))
        for row, g in zip(rows, got, strict=True):
            w = want[row]
            if not np.isfinite(g).all():
                return dict(record, ok=False,
                            why=f"sample {i}: logits not finite")
            if np.isnan(w).all():
                unanswered += 1
                continue
            if not np.isfinite(w).all():
                return dict(record, ok=False, why=f"sample {i}: the "
                            f"reference's logits at {row} are not finite")
            worst = max(worst, ar.max_rel_err(g, w))
            worst_rms = max(worst_rms, ar.rms_rel_err(g, w))
            compared += 1
    ok = worst <= tolerance and worst_rms <= rms_tolerance
    return dict(
        record, ok=ok, max_rel_err=worst, rms_rel_err=worst_rms,
        compared=compared, unanswered=unanswered, why=None if ok else
        f"engine vs reference logits: max {worst:.4f} of range "
        f"(<= {tolerance}), rms {worst_rms:.4f} (<= {rms_tolerance})")


def checked_sample(records, check: dict, seed: int) -> list:
    """The prompts the check replays: ``check["requests"]`` of the requests
    that finished as asked, drawn from the seed among those whose prompt
    has at least ``min_prompt_tokens`` tokens (0 where the configuration
    names none) and at most ``max_prompt_tokens``."""
    least = check.get("min_prompt_tokens", 0)
    ok_records = [r for r in records if r.ok
                  and least <= len(r.req.prompt) <= check["max_prompt_tokens"]]
    if not ok_records:
        return []
    picks = np.random.default_rng([seed, 0x636b]).choice(
        len(ok_records), size=min(check["requests"], len(ok_records)),
        replace=False)
    return [ok_records[i].req.prompt for i in picks]


def causal_qk_pairs(new, seen) -> int:
    """Query-key pairs of a put's rows under a causal mask: each of a
    row's ``new`` positions sees the ``seen`` before the put and those of
    the put up to itself. A block type whose mask is another gives its own
    ``qk_pairs(new, seen)`` (int64 arrays in, an integer out)."""
    return int((new * seen + new * (new + 1) // 2).sum())


# -------------------------------------------------------------------- run

def run(info: dict, args, watch, process_t0: float) -> dict:
    mix, wl = info["traffic"], info["workload"]
    traced = bool(args.trace)
    cfg, params, engine = build(info, args.seed)
    kv_blocks, block = engine.config.kv_blocks, info["block"]
    probe = Probe()
    # warm up first, instrument after: a wrapper is one more frame on the
    # Python stack, the stack is in the locations a Mosaic kernel carries
    # into the compile cache's key, and a traced run would compile every
    # program again
    warm_calls = warm_up(engine, block)
    if traced:
        _instrument_engine(probe, engine,
                           getattr(block, "qk_pairs", causal_qk_pairs))

    from deepspeed_tpu.serving import ServingConfig, ServingFrontend

    serving = dict(wl.get("serving", {}))
    if traced:      # the program's own request spans, read in traced runs
        serving["telemetry"] = {"enabled": True, "max_spans": 1 << 20}
    fe = ServingFrontend([engine], ServingConfig(**serving))
    try:
        if traced:
            sched = fe.router.replicas[0].scheduler
            probe.wrap(sched, "step", "step")
        stream = traffic.generator(info).requests(
            mix, cfg.vocab_size, args.seed, rate_rps=wl.get("rate_rps"))
        compiles_before = watch.count
        t0 = time.monotonic() + 0.05
        w0 = t0 + float(mix["preroll_s"])
        w1 = w0 + float(args.seconds)
        trace = None
        if traced:
            span = min(float(wl.get("trace_s", 5.0)), float(args.seconds))
            trace = TraceWindow(args.trace_dir, w1 - span, w1)
        setup_s = w0 - process_t0
        if mix["loop"] == "open":
            records = open_loop(fe, stream, t0, w1)
        else:
            records = closed_loop(fe, stream, int(mix["clients"]), w1)
        compiles_in_window = watch.count - compiles_before
        unfinished = drain(fe, records, float(mix["drain_s"]))
        peak = memory_peak_bytes(1)
        xplane = trace.finish() if trace is not None else None

        in_window = [r for r in records if w0 <= r.due < w1]
        failed = [r for r in in_window if not r.ok]
        # every block back: the frontend flushes a finished or cancelled
        # request on the worker's next pass
        deadline = time.monotonic() + 5.0
        while engine.state_manager.available_blocks != kv_blocks \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        blocks_back = engine.state_manager.available_blocks == kv_blocks
        program_spans = fe.tracer.export() if traced else []
    finally:
        fe.shutdown(drain=False, timeout=30)

    check = info["config"]["check"]
    sample = checked_sample(records, check, args.seed)
    logits = check_logits(engine, params, info, sample,
                          check["decode_steps"], check["tolerance"],
                          check["rms_tolerance"]) \
        if sample else {"ok": False, "why": "no finished request"}
    blocks_back = blocks_back and \
        engine.state_manager.available_blocks == kv_blocks
    # a recurrent layer's state slots (none without one): every sequence
    # of the window and of the check has been flushed by now
    slots_held = int(engine.occupancy()["state_slots_used"])
    why = [w for w in (
        logits.get("why"),
        None if blocks_back else "KV blocks were not all returned",
        None if slots_held == 0 else
        f"{slots_held} state slots were not returned",
        None if compiles_in_window == 0 else
        f"{compiles_in_window} compilations inside the window",
        None if in_window else "no request was due in the window") if w]
    return {
        "correct": not why, "why_not": why,
        "attempted": len(in_window), "failed": len(failed),
        "setup_s": setup_s, "memory_peak_bytes": peak,
        "window": (w0, w1), "records": records, "xplane": xplane,
        "trace_marks": trace.marks if trace is not None else None,
        "probe": probe, "program_spans": program_spans,
        # each number ``correct`` compared, beside its limit
        "checks": {
            "logits_max_rel_err": (logits.get("max_rel_err"),
                                   check["tolerance"]),
            "logits_rms_rel_err": (logits.get("rms_rel_err"),
                                   check["rms_tolerance"]),
            "kv_blocks_missing": (
                kv_blocks - engine.state_manager.available_blocks, 0),
            "state_slots_held": (slots_held, 0),
            "compiles_in_window": (compiles_in_window, 0)},
        "counters": {"kv_blocks": kv_blocks,
                     "compiles_in_window": compiles_in_window,
                     "unfinished_at_drain": unfinished,
                     "state_slots_held": slots_held,
                     "warm_up_calls": warm_calls,
                     "requests_sent": len(records),
                     "longest_silence_ms": 1e3 * ar.longest_silence(
                         (t for r in records for t in r.times), w0, w1),
                     "logits_check": logits,
                     "latency_ms": _latency_table(in_window)},
        "arch": info["config"]["transformer_config"], "chips": 1,
    }


def _latency_table(records) -> dict:
    """Other percentiles of the window's requests, for the record (the
    metrics proper are read by the readers)."""
    ttft = [ar.ttft_ms(r.due, r.times[0] if r.ok else None) for r in records]
    tpot = [g for g in (ar.tpot_ms(r.times) for r in records if r.ok)
            if g is not None]
    table = {}
    for name, xs in (("ttft", ttft), ("tpot", tpot)):
        if xs:
            table[name] = {f"p{p}": ar.percentile(xs, p)
                           for p in (50, 75, 90, 99)}
            table[name]["mean"] = sum(xs) / len(xs)
    return table


def _instrument_engine(probe: Probe, engine, qk_pairs=causal_qk_pairs) -> None:
    """Spans round ``engine.put`` and the paged forward (with its bucket
    shape, its valid tokens and its query-key pairs as the block type's
    mask counts them), and the pool's free blocks sampled after every
    put."""
    def forward_attrs(params, kv, tokens, *rest):
        s, c = tokens.shape
        batch = engine.batch           # the host-side arrays of this put
        n = batch.current_sequences
        new = batch.n_tokens[:n].astype(np.int64)
        seen = batch.start_pos[:n].astype(np.int64)
        return {"tag": f"{s}x{c}", "seqs": s, "chunk": c, "rows": n,
                "valid_tokens": int(new.sum()),
                # keys a row's queries may see, and query-key pairs
                "kv_read_tokens": int((seen + new).sum()),
                "qk_pairs": int(qk_pairs(new, seen))}

    probe.wrap(engine.paged, "forward", "forward", attrs=forward_attrs)
    probe.wrap(engine, "put", "put", after=lambda: probe.sample(
        "free_blocks", engine.state_manager.available_blocks))
