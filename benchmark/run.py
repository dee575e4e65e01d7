"""One cell, once:

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Refuses (exit 2, no result line) off the chip or with fewer chips than the
cell asks for. Builds the weights on the device from ``--seed``, warms up
every shape, measures for ``--seconds``, checks the outputs, and prints as
the last line of stdout one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced). With
``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics. Each number ``correct`` compared
stands beside its limit under ``checks``, the line's last key, and in the
last lines of stderr. What else a run learned (why it is not correct,
compile seconds and cache hits, counters) is printed as an
``{"extra": …}`` line before it.
"""

from __future__ import annotations

import time

_PROCESS_T0 = time.monotonic()      # set-up is counted from here

import argparse                      # noqa: E402
import json                          # noqa: E402
import math                          # noqa: E402
import os                            # noqa: E402
import sys                           # noqa: E402

from . import manifest as mf         # noqa: E402


class Context:
    """What a metric's reader is given: the runner's result, the cell's
    files, the device, and (traced runs) the reduced trace."""

    def __init__(self, result: dict, info: dict, device: dict):
        self.result, self.info, self.device = result, info, device
        self._trace = None

    @property
    def trace(self):
        """The reduced profiler trace; None in an untraced run, and off
        the chip (a rehearsal): device numbers come from the device."""
        if self._trace is None and self.result.get("xplane") \
                and self.device["platform"] == "tpu":
            from . import trace as tr

            self._trace = tr.summarize(tr.load_xplane(self.result["xplane"]),
                                       chips=self.result["chips"])
        return self._trace


def read_metric(bench_dir: str, group_dir: str, name: str, ctx: Context):
    """Call ``reduce(ctx)`` of the reader found by the metric's name; a
    reader that finds nothing to read returns None."""
    return mf.find_module(bench_dir, group_dir, name).reduce(ctx)


def main(argv=None, root: str = mf.CHECKOUT, platform: str = "tpu") -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    manifest = mf.load(root)
    info = mf.resolve(manifest, args.workload, root)
    chips = int(info["cell"]["chips"])
    if not os.path.isdir(os.path.join(root, "deepspeed_tpu")):
        print("benchmark: the program (deepspeed_tpu/) is not in this "
              "checkout", file=sys.stderr)
        return 2

    from . import device as dev

    cache_dir = dev.enable_compile_cache()
    record = dev.require_chips(chips, platform)
    watch = dev.CompileWatch()
    args.trace_dir = os.path.join(root, "chiprun_out", "traces",
                                  f"{args.workload}.seed{args.seed}")

    runner = info["workload"]["runner"]
    if runner == "serve":
        from . import serve_runner as r
    elif runner == "train":
        from . import train_runner as r
    else:
        raise mf.ManifestError(f"unknown runner {runner!r}")
    result = r.run(info, args, watch, _PROCESS_T0)

    ctx = Context(result, info, record)
    group, group_dir = (("per_layer", "layer_metrics") if args.trace
                        else ("end_to_end", "end_to_end"))
    metrics = {}
    for m in mf.metrics_for(manifest, group, args.workload):
        value = read_metric(info["bench_dir"], group_dir, m["name"], ctx)
        if value is None:
            continue
        if not math.isfinite(value):
            result["why_not"].append(f"{m['name']} is {value}")
            result["correct"] = False
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    device = {"platform": record["platform"], "kind": record["kind"],
              "count": record["count"],
              "memory_peak_bytes": result["memory_peak_bytes"]}
    line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]),
            "metrics": metrics, "device": device}
    if args.trace and ctx.trace is not None:
        device["busy_s"] = ctx.trace["busy_s"]
        device["window_s"] = ctx.trace["window_s"]
        line["breakdown"] = {"device_ops": ctx.trace["device_ops"][:10],
                             "idle_gaps": ctx.trace["idle_gaps"][:10]}
    compiles = watch.snapshot()
    extra = {"why_not": result["why_not"],
             "workload": args.workload, "seed": args.seed,
             "seconds": args.seconds, "trace": args.trace,
             "devices_present": record["count"],
             "compile_cache_dir": cache_dir,
             "compile_seconds": compiles["seconds"],
             "compilations": compiles["count"],
             "compile_cache_hits": compiles["hits"],
             "counters": result["counters"]}
    line["checks"] = {name: {"value": value, "limit": limit}
                      for name, (value, limit) in result["checks"].items()}
    print(json.dumps({"extra": extra}), flush=True)
    print(json.dumps(line), flush=True)
    for name, (value, limit) in result["checks"].items():
        print(f"check {name}: {value} (limit {limit})", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
