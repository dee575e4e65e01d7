"""What the metric readers share. A reader (``end_to_end/<name>.py``,
``layer_metrics/<name>.py``) is a file of its own with one
``reduce(ctx)``; the arithmetic it names lives here and in
``arithmetic.py``, so two metrics that differ only in the cell they are
read in (``pad_ratio`` below the knee, ``sat_pad_ratio`` at saturation)
are the same computation. Everything returns None when there is nothing
to read — the harness then leaves the metric out."""

from __future__ import annotations

from collections import Counter, defaultdict

from . import arithmetic as ar
from . import dispatch_readers, peaks, trace

#: the flash-attention forward and its two backward kernels, as named in
#: ``ops/flash_attention.py``
FLASH_KERNELS = ("kernel:flash_attention_fwd", "kernel:flash_attention_dq",
                 "kernel:flash_attention_dkv")


def due_in_window(ctx):
    w0, w1 = ctx.result["window"]
    return [r for r in ctx.result.get("records", []) if w0 <= r.due < w1]


# ------------------------------------------------------------ end to end

def ttft_percentile_ms(ctx, p):
    recs = due_in_window(ctx)
    if not recs:
        return None
    return ar.percentile(
        [ar.ttft_ms(r.due, r.times[0] if r.ok else None) for r in recs], p)


def tpot_percentile_ms(ctx, p):
    gaps = [ar.tpot_ms(r.times) for r in due_in_window(ctx) if r.ok]
    gaps = [g for g in gaps if g is not None]
    return ar.percentile(gaps, p) if gaps else None


def serve_tokens_per_s(ctx):
    w0, w1 = ctx.result["window"]
    n = sum(ar.count_in_window(r.times, w0, w1)
            for r in ctx.result.get("records", []))
    return n / (w1 - w0) if n else None


def train_tokens_per_s_chip(ctx):
    steps = ctx.result.get("step_seconds")
    if not steps:
        return None
    w0, w1 = ctx.result["window"]
    return (len(steps) * ctx.result["tokens_per_step"] / (w1 - w0)
            / ctx.result["chips"])


# ----------------------------------------------------- host spans, counters

def _probe_spans(ctx, name):
    probe = ctx.result.get("probe")
    if probe is None:
        return []
    return probe.named(name, *ctx.result["window"])


def gen_late_percentile_ms(ctx, p):
    recs = due_in_window(ctx)
    return ar.percentile([(r.sent - r.due) * 1e3 for r in recs], p) \
        if recs else None


def queue_wait_percentile_ms(ctx, p):
    """Submit → handed to the scheduler: from the start of the program's
    ``queue`` span to the end of its ``admit`` span, per request."""
    w0, w1 = ctx.result["window"]
    by_request = defaultdict(dict)
    for s in ctx.result.get("program_spans", []):
        if s["name"] in ("queue", "admit") and s.get("t_end") is not None:
            by_request[s["trace_id"]][s["name"]] = s
    waits = [(v["admit"]["t_end"] - v["queue"]["t_start"]) * 1e3
             for v in by_request.values()
             if len(v) == 2 and w0 <= v["queue"]["t_start"] < w1]
    return ar.percentile(waits, p) if waits else None


def batch_seqs_mean(ctx):
    """Sequences per forward, from the program's ``forward`` spans."""
    w0, w1 = ctx.result["window"]
    n = [s["attrs"]["n_seqs"] for s in ctx.result.get("program_spans", [])
         if s["name"] == "forward" and w0 <= s["t_start"] < w1]
    return sum(n) / len(n) if n else None


def host_step_share(ctx):
    """Share (%) of ``scheduler.step`` wall time in which the device ran
    nothing: the step's host work (packing, sampling, uploads, the logits
    on their way back, streaming) that no device work hides. From the
    trace: idle gaps that fall inside a ``bench:step`` annotation over the
    annotations' total length. (``engine.put`` returns before the device
    is done, so host spans alone cannot tell the two apart.)"""
    if ctx.trace is None:
        return None
    t0, t1 = ctx.trace["window"]
    steps = ar.union_seconds(ar.clip_intervals(
        ((e["start"], e["start"] + e["dur"]) for e in ctx.trace["host"]
         if e["name"] == "bench:step"), t0, t1))
    idle = sum(v for k, v in ctx.trace["idle_gaps"]
               if k in ("bench:step", "bench:put", "bench:forward"))
    return 100.0 * idle / steps if steps else None


def pad_ratio(ctx):
    """Positions the forward computed (bucket S·C) per valid token."""
    fw = [a for *_, a in _probe_spans(ctx, "forward")]
    valid = sum(a["valid_tokens"] for a in fw)
    return sum(a["seqs"] * a["chunk"] for a in fw) / valid if valid else None


def kv_blocks_peak_share(ctx):
    """Largest share (%) of the KV pool in use at the end of a step."""
    probe = ctx.result.get("probe")
    w0, w1 = ctx.result["window"]
    free = [v for t, n, v in (probe.samples if probe else [])
            if n == "free_blocks" and w0 <= t < w1]
    total = ctx.result["counters"].get("kv_blocks")
    return 100.0 * (1 - min(free) / total) if free and total else None


def forward_device_ms(ctx, mixed: bool):
    """Median device time of the paged forward's program at the most
    frequent [S, 1] (decode) or [S, max chunk] (mixed) bucket of the
    traced window. Each program execution of the trace's XLA Modules line
    is matched to its ``ds:dispatch`` by order
    (``dispatch_readers.forward_device_ms``): with a step in flight a
    forward starts on the device about a step after its dispatch, so the
    annotation that began nearest to a module's start is its neighbour's
    wherever the bucket changes, and a window whose widest chunk forwards
    each stand between two one-token steps had nothing to read. Only a
    trace that cannot be paired by order (no ``ds:dispatch``: a program
    from before the span, a recorded trace) is still read by nearness."""
    if ctx.trace is None:
        return None
    by_order = dispatch_readers.forward_device_ms(ctx, mixed)
    if by_order is not None:
        return by_order
    t0, t1 = ctx.trace["window"]
    tags = Counter()
    for e in ctx.trace["host"]:
        if e["name"].startswith("bench:forward[") and t0 <= e["start"] < t1:
            s, c = e["name"][len("bench:forward["):-1].split("x")
            tags[(int(s), int(c))] += 1
    widest = max((c for _, c in tags), default=0)
    want = {k: v for k, v in tags.items()
            if (k[1] == widest and widest > 1 if mixed else k[1] == 1)}
    if not want:
        return None
    s, c = max(want, key=want.get)
    secs = trace.module_seconds(ctx.trace, "forward", tag=f"{s}x{c}")
    return ar.median(secs) * 1e3 if secs else None


def kernel_roofline(ctx, names, least_seconds, per_module=None):
    """Kernels' share (%) of their roofline: ``least_seconds``, the least
    time the chip could take for the calls made (from their shapes), over
    the device time of the events of the custom calls ``names``
    (``kernel:<name>``, as ``breakdown.device_ops`` prints a kernel's
    ``name=``) — their own and no other kernel's, so a second kernel in
    the same program moves nothing here. Without ``per_module`` both are
    of the traced window. With it, ``least_seconds`` is that of one
    execution of the program whose name holds ``per_module``, the time is
    that of the named kernels' events inside each whole execution in the
    window, and the median over the executions is given."""
    if ctx.trace is None or not least_seconds:
        return None
    if per_module is None:
        spent = sum(ctx.trace["kernel_seconds"].get(n, 0.0) for n in names)
        return 100.0 * least_seconds / spent if spent else None
    t1 = ctx.trace["window"][1]
    mine = [k for k in ctx.trace["kernels"] if k["family"] in names]
    shares = []
    for m in ctx.trace["modules"]:
        a, b = m["start"], m["start"] + m["dur"]
        if per_module not in m["name"] or b > t1:
            continue
        spent = sum(k["dur"] for k in mine
                    if k["device"] == m["device"] and a <= k["start"] < b)
        if spent:
            shares.append(100.0 * least_seconds / spent)
    return ar.median(shares) if shares else None


def paged_attention_roofline(ctx):
    """The paged-attention kernel's share (%) of its roofline over the
    traced window: the least time the chip could take for the calls the
    window made (from their shapes: the larger of FLOPs over the bf16
    peak and bytes over the HBM peak, per layer) over the device time of
    the kernel's events."""
    marks = ctx.result.get("trace_marks")
    if ctx.trace is None or not marks:
        return None
    arch, kind = ctx.result["arch"], ctx.device["kind"]
    least = 0.0
    for _, t0, _, a in ctx.result["probe"].named("forward", *marks):
        cost = peaks.paged_attention_cost(arch, a["valid_tokens"],
                                          a["kv_read_tokens"], a["qk_pairs"])
        least += arch["num_layers"] * peaks.roofline_seconds(cost, kind)
    return kernel_roofline(ctx, ("kernel:paged_attention",), least)


# ------------------------------------------------------------------ train

def step_percentile_ms(ctx, p):
    steps = ctx.result.get("step_seconds")
    return ar.percentile(steps, p) * 1e3 if steps else None


def mfu_percent(ctx):
    """Model FLOPs (from shapes, recomputation not counted) × tokens/s
    over chips × the published bf16 peak."""
    rate = train_tokens_per_s_chip(ctx)
    if rate is None or ctx.device["platform"] == "cpu":
        return None             # a rehearsal has no chip to relate to
    flops = peaks.train_flops_per_token(ctx.info["block"], ctx.result["arch"],
                                        ctx.result["sequence_tokens"])
    return 100.0 * flops * rate / peaks.peaks(ctx.device["kind"])["flops_bf16"]


def peak_hbm_gb(ctx):
    peak = ctx.result.get("memory_peak_bytes")
    return peak / 1e9 if peak else None


def flash_attention_roofline(ctx):
    """The flash kernels' share (%) of their roofline in the train step:
    the least time for one layer's causal attention forward and backward
    at the step's shapes (FLOP-bound; the remat pass's second forward is
    not needed work and is not counted) × layers, over the device time of
    the three flash kernels inside each whole micro-step program of the
    traced window."""
    if ctx.trace is None:
        return None
    arch = ctx.result["arch"]
    per_chip = ctx.result["tokens_per_step"] // ctx.result["chips"] \
        // ctx.result["sequence_tokens"]
    least = arch["num_layers"] * peaks.roofline_seconds(
        peaks.flash_attention_cost(arch, per_chip,
                                   ctx.result["sequence_tokens"]),
        ctx.device["kind"])
    return kernel_roofline(ctx, FLASH_KERNELS, least, per_module="micro")


def collective_share(ctx, exposed: bool):
    if ctx.trace is None:
        return None
    key = "collective_exposed_s" if exposed else "collective_s"
    return 100.0 * ctx.trace[key] / ctx.trace["window_s"]
