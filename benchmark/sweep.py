"""Find an open-loop cell's knee, once, when the cell is defined:

    python3 -m benchmark.sweep --workload <cell> --rates 2,2.5,3 --seconds 30

One process builds the engine and warms it up once, then offers each rate
in turn (a seed of its own per rate) and prints one JSON line per rate:
arrivals and completions in the window, and the tails of the window's
first and second half. The knee is the highest rate at which completions
keep up with arrivals and TTFT in the second half is not worse than in
the first. A benchmark PR writes 0.8 x the knee into the cell's file; the
benchmark itself never searches.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from . import arithmetic as ar
from . import device as dev
from . import manifest as mf
from . import serve_runner as sr
from . import traffic


def half_stats(records, a, b):
    recs = [r for r in records if a <= r.due < b]
    if not recs:
        return {"n": 0}
    ttft = [ar.ttft_ms(r.due, r.times[0] if r.times else None) for r in recs]
    tpot = [g for g in (ar.tpot_ms(r.times) for r in recs if r.ok)
            if g is not None]
    return {"n": len(recs), "ok": sum(r.ok for r in recs),
            "ttft_p50_ms": ar.percentile(ttft, 50),
            "ttft_p90_ms": ar.percentile(ttft, 90),
            "tpot_p90_ms": ar.percentile(tpot, 90) if tpot else None}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=100)
    args = ap.parse_args(argv)
    manifest = mf.load()
    info = mf.resolve(manifest, args.workload)
    dev.enable_compile_cache()
    record = dev.require_chips(1)
    watch = dev.CompileWatch()
    mix = info["traffic"]
    if mix.get("loop") != "open":
        raise SystemExit("only an open-loop cell has a knee to find")

    from deepspeed_tpu.serving import ServingConfig, ServingFrontend

    cfg, params, engine = sr.build(info, args.seed)
    sr.warm_up(engine, info["block"])
    fe = ServingFrontend([engine],
                         ServingConfig(**info["workload"].get("serving", {})))
    try:
        for i, rate in enumerate(float(x) for x in args.rates.split(",")):
            stream = traffic.generator(info).requests(
                mix, cfg.vocab_size, args.seed + i, rate_rps=rate)
            t0 = time.monotonic() + 0.05
            w0 = t0 + float(mix["preroll_s"])
            w1 = w0 + args.seconds
            compiles = watch.count
            records = sr.open_loop(fe, stream, t0, w1)
            compiles = watch.count - compiles
            done_by_w1 = sum(1 for r in records
                             if w0 <= r.due < w1 and sr._finished(r.handle))
            unfinished = sr.drain(fe, records, float(mix["drain_s"]))
            mid = (w0 + w1) / 2
            tokens = sum(ar.count_in_window(r.times, w0, w1)
                         for r in records)
            print(json.dumps({
                "rate_rps": rate, "device": record["kind"],
                "due_in_window": sum(1 for r in records if w0 <= r.due < w1),
                "finished_by_window_end": done_by_w1,
                "unfinished_after_drain": unfinished,
                "output_tok_s": tokens / args.seconds,
                "compiles_in_window": compiles,
                "longest_silence_ms": 1e3 * ar.longest_silence(
                    (t for r in records for t in r.times), w0, w1),
                "first_half": half_stats(records, w0, mid),
                "second_half": half_stats(records, mid, w1),
                "gen_late_p99_ms": ar.percentile(
                    [(r.sent - r.due) * 1e3 for r in records], 99)}),
                flush=True)
            if unfinished:
                break       # past the knee: what follows would start loaded
    finally:
        fe.shutdown(drain=False, timeout=30)
    return 0


if __name__ == "__main__":
    sys.exit(main())
