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


def warm_up(engine) -> int:
    """Run every shape the traffic can reach through ``engine.put``, so
    that nothing compiles in the window: each [S, C] bucket once, and each
    sequence count 1..max once (the slice of the logits to the real rows
    is a small program of its own per count). KV blocks go back at once."""
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
    return calls


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

def check_logits(engine, params, info, sample, decode_steps: int,
                 tolerance: float, rms_tolerance: float) -> dict:
    """A seeded sample of requests, prefill in chunks and then decode
    through the cache, the engine's logits at every step against the full
    forward of the configuration's block reference (``info["block"]``)
    over the same tokens: the largest disagreement relative to the range,
    and the RMS relative to the RMS (a lower precision shows there first)."""
    import jax

    block, arch = info["block"], info["config"]["transformer_config"]
    chunk = engine.config.max_chunk_tokens
    width = -(-max(len(p) + decode_steps for p in sample) // 256) * 256
    ref_fn = jax.jit(lambda p, t: block.logits(p, t, arch))
    worst = worst_rms = 0.0
    for i, prompt in enumerate(sample):
        uid = _OWN_UID + (1 << 20) + i
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


# -------------------------------------------------------------------- run

def run(info: dict, args, watch, process_t0: float) -> dict:
    mix, wl = info["traffic"], info["workload"]
    traced = bool(args.trace)
    cfg, params, engine = build(info, args.seed)
    kv_blocks = engine.config.kv_blocks
    probe = Probe()
    # warm up first, instrument after: a wrapper is one more frame on the
    # Python stack, the stack is in the locations a Mosaic kernel carries
    # into the compile cache's key, and a traced run would compile every
    # program again
    warm_calls = warm_up(engine)
    if traced:
        _instrument_engine(probe, engine)

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

    rng = np.random.default_rng([args.seed, 0x636b])
    check = info["config"]["check"]
    ok_records = [r for r in records if r.ok
                  and len(r.req.prompt) <= check["max_prompt_tokens"]]
    picks = rng.choice(len(ok_records),
                       size=min(check["requests"], len(ok_records)),
                       replace=False) if ok_records else []
    logits = check_logits(engine, params, info,
                          [ok_records[i].req.prompt for i in picks],
                          check["decode_steps"], check["tolerance"],
                          check["rms_tolerance"]) \
        if len(picks) else {"ok": False, "why": "no finished request"}
    blocks_back = blocks_back and \
        engine.state_manager.available_blocks == kv_blocks
    why = [w for w in (
        logits.get("why"),
        None if blocks_back else "KV blocks were not all returned",
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
        "counters": {"kv_blocks": kv_blocks,
                     "compiles_in_window": compiles_in_window,
                     "unfinished_at_drain": unfinished,
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


def _instrument_engine(probe: Probe, engine) -> None:
    """Spans round ``engine.put`` and the paged forward (with its bucket
    shape and valid tokens), and the pool's free blocks sampled after
    every put."""
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
                "qk_pairs": int((new * seen + new * (new + 1) // 2).sum())}

    probe.wrap(engine.paged, "forward", "forward", attrs=forward_attrs)
    probe.wrap(engine, "put", "put", after=lambda: probe.sample(
        "free_blocks", engine.state_manager.available_blocks))
