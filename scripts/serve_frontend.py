#!/usr/bin/env python3
"""Run one federated serving frontend process (docs/SERVING.md
"Frontend federation").

The process builds a :class:`ServingFrontend` over seeded local engines
and — when the spec's serving config enables ``fabric.federation`` with
``fabric.listen`` — exports its replica pool to peer frontends. Peers
adopt the exports as routable federated members; killing this process
exercises the cross-frontend failover path on every peer.

    python scripts/serve_frontend.py --spec spec.json

``spec.json``::

    {
      "model":      {... TransformerConfig kwargs ...},
      "engine":     {... RaggedInferenceEngineConfig kwargs ...},
      "seed":       0,              # params = model.init(PRNGKey(seed))
      "n_replicas": 1,              # local engines behind this frontend
      "serving":    {... ServingConfig dict; federation topology lives
                      in its fabric block: "fabric": {"enabled": true,
                      "listen": "127.0.0.1:0", "federation":
                      {"enabled": true, "peers": [...]}} ...}
    }

One process per chip: this process builds its local engines on the
accelerator it finds, and a chip belongs to one process. Replica servers
it fronts on the same host (``scripts/serve_replica.py``) each need a
chip of their own — one started on the chip this process holds exits
with an error instead of serving.

Seeded init keeps byte-parity testable across frontends: every frontend
(and every replica server) built from the same spec holds identical
weights, so greedy streams must match to the token no matter which
frontend's replica served them.

On startup the process prints one machine-readable line::

    FEDERATION_LISTENING <host>:<port>

(the parent parses it to learn an ephemeral port; ``none`` when the spec
does not export). The process serves until killed.
"""

import argparse
import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        epilog="One process per chip: this process's local engines hold "
               "the accelerator it finds; every serve_replica.py process "
               "it fronts on the same host needs a chip of its own.")
    ap.add_argument("--spec", required=True, help="spec JSON path")
    args = ap.parse_args(argv)

    with open(args.spec) as fh:
        spec = json.load(fh)

    import jax

    from deepspeed_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2, RaggedInferenceEngineConfig)
    from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig
    from deepspeed_tpu.serving.config import ServingConfig
    from deepspeed_tpu.serving.frontend import ServingFrontend

    model = CausalLM(TransformerConfig(**spec["model"]))
    params = model.init(jax.random.PRNGKey(int(spec.get("seed", 0))))
    engines = [
        InferenceEngineV2(
            model, params=params,
            config=RaggedInferenceEngineConfig(**spec.get("engine", {})))
        for _ in range(int(spec.get("n_replicas", 1)))]

    config = ServingConfig(**spec.get("serving", {}))
    fe = ServingFrontend(engines, config)
    addr = fe.federation_address
    print(f"FEDERATION_LISTENING {addr or 'none'}", flush=True)
    try:
        while True:
            time.sleep(1.0)
    except KeyboardInterrupt:
        pass
    finally:
        fe.shutdown(drain=False, timeout=10)
    return 0


if __name__ == "__main__":
    sys.exit(main())
