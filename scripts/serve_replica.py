#!/usr/bin/env python3
"""Run one fabric replica server process (docs/SERVING.md "Multi-host
serving").

The process owns its own JAX runtime — on a TPU host the engine it
builds can be a TP-sharded mesh slice spanning that host's chips — and
serves the fabric RPC protocol (deepspeed_tpu/serving/fabric/server.py)
for a frontend to adopt as a :class:`RemoteHandle` replica.

One process per chip: a chip belongs to the first process that touches
JAX on it, so each replica process needs a chip (or a host's worth of
chips) of its own, not one that a frontend with local engines or another
replica already holds. A replica that finds its chip taken exits with
code 3 and says so; it does not wait for the chip.

    python scripts/serve_replica.py --spec spec.json \
        [--listen 127.0.0.1:0] [--replica-id 0] [--heartbeat-s 1.0]

``spec.json``::

    {
      "model":      {... TransformerConfig kwargs ...},
      "engine":     {... RaggedInferenceEngineConfig kwargs ...},
      "seed":       0,              # params = model.init(PRNGKey(seed))
      "checkpoint": null,           # OR a training checkpoint dir —
                                    # params loaded via runtime/
                                    # checkpointing.load_params_for_model
                                    # (overrides seed; a missing or
                                    # model-mismatched manifest aborts
                                    # boot with a descriptive error)
      "model_id":   "default",      # pool name advertised in the fabric
                                    # hello — a frontend adopting this
                                    # replica under a DIFFERENT model
                                    # name refuses it (ModelMismatch)
      "mesh":       null,           # OR {axis: size, ...} (e.g.
                                    # {"tensor": 4}) — the engine is
                                    # built over a MeshTopology spanning
                                    # this host's devices; -1 means "all
                                    # remaining". Too few local devices
                                    # aborts boot with a descriptive
                                    # required-vs-available error
      "serving":    {... ServingConfig dict (engine blocks, speculative,
                      disaggregation/handoff chunking, faults...) ...}
    }

Seeded init makes byte-parity testable: a frontend-side engine built
from the same spec holds identical weights, so local-vs-remote greedy
streams must match to the token. Production deployments swap ``seed``
for the ``checkpoint`` field — the protocol does not care where the
params came from.

On startup the process prints one machine-readable line::

    FABRIC_LISTENING <advertise_host>:<port>

(the parent parses it to learn an ephemeral port; the advertised host
rides ``comm._routable_ip`` — never 127.0.0.1 when a route exists —
unless the bind address was explicit).
"""

import argparse
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--spec", required=True, help="spec JSON path")
    ap.add_argument("--listen", default="127.0.0.1:0")
    ap.add_argument("--replica-id", type=int, default=0)
    ap.add_argument("--heartbeat-s", type=float, default=1.0)
    ap.add_argument("--loopback-ok", action="store_true",
                    help="advertise the literal bind host even if it is "
                         "loopback (single-host tests/bench)")
    args = ap.parse_args(argv)

    with open(args.spec) as fh:
        spec = json.load(fh)

    import jax

    from deepspeed_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2, RaggedInferenceEngineConfig)
    from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig
    from deepspeed_tpu.serving.config import ServingConfig
    from deepspeed_tpu.serving.fabric.server import ReplicaServer
    from deepspeed_tpu.serving.fabric.transport import advertised_address
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    try:
        jax.devices()
    except RuntimeError as e:
        # libtpu refuses a second process on a held chip at once ("Unable
        # to initialize backend 'tpu': ... libtpu multi-process lockfile")
        print("serve_replica: no accelerator for this process — a chip "
              "belongs to one process, and each replica server (and a "
              "frontend that builds local engines) needs one of its own: "
              f"{e}", file=sys.stderr)
        return 3

    mesh = None
    if spec.get("mesh"):
        from deepspeed_tpu.parallel.topology import MeshTopology
        sizes = {str(k): int(v) for k, v in dict(spec["mesh"]).items()}
        need = 1
        for v in sizes.values():
            if v != -1:
                need *= v
        have = len(jax.devices())
        if have < need or have % max(need, 1):
            print(f"serve_replica: mesh spec {sizes} requires "
                  f"{'a multiple of ' if -1 in sizes.values() else ''}"
                  f"{need} device(s) but this host has {have}: "
                  f"{[str(d) for d in jax.devices()]}", file=sys.stderr)
            return 2
        mesh = MeshTopology.build(**sizes)

    model = CausalLM(TransformerConfig(**spec["model"]))
    if spec.get("checkpoint"):
        from deepspeed_tpu.runtime.checkpointing import load_params_for_model
        params = load_params_for_model(model, spec["checkpoint"])
    else:
        params = model.init(jax.random.PRNGKey(int(spec.get("seed", 0))))

    def engine_factory():
        return InferenceEngineV2(
            model, params=params,
            config=RaggedInferenceEngineConfig(**spec.get("engine", {})),
            mesh=mesh)

    config = ServingConfig(**spec.get("serving", {}))
    server = ReplicaServer(engine_factory, config, listen=args.listen,
                           replica_id=args.replica_id,
                           heartbeat_s=args.heartbeat_s,
                           max_frame_bytes=config.fabric.max_frame_bytes,
                           model_id=str(spec.get("model_id", "default")))
    host = (server.listen_host if args.loopback_ok
            else advertised_address(server.listen_host,
                                    server.port).rsplit(":", 1)[0])
    print(f"FABRIC_LISTENING {host}:{server.port}", flush=True)
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        server.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
