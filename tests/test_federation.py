"""Frontend federation (ISSUE 18, docs/SERVING.md "Frontend
federation").

Two-tier serving fleet: a frontend with ``fabric.federation.enabled``
exports a slice of its LOCAL replica pool on ``fabric.listen`` while
adopting peer frontends' exports as routable federated members. Covers
the topology edges (self-peering refusal, wrong hello role, stale-epoch
rejection with newer-epoch supersession, no transitive re-export), the
shared pool (greedy byte-parity through an adopter with and without
local engines, per-peer capacity accounting via the status stream's
``active_total`` and ``peer_max_inflight``), cross-frontend failover
(killing a REAL subprocess frontend mid-burst — the adopter's in-flight
federated work resumes byte-losslessly on its local replica), local
evacuation draining onto a peer, removal of a federated member
requeueing its mirrors, and ``federation`` disabled being byte-for-byte
the single-frontend fabric stack.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest
from test_fabric import first_line

from deepspeed_tpu.inference.v2.engine_v2 import (InferenceEngineV2,
                                                  RaggedInferenceEngineConfig)
from deepspeed_tpu.serving import ServingConfig, ServingFrontend
from deepspeed_tpu.serving.fabric.federation import (FederatedHandle,
                                                     FederationPeer,
                                                     FederationRefused,
                                                     derive_frontend_id)
from deepspeed_tpu.serving.fabric.transport import FabricError, dial

VOCAB = 128
MODEL_KW = dict(vocab_size=VOCAB, hidden_size=64, intermediate_size=128,
                num_layers=2, num_heads=2, max_seq_len=256, norm="rmsnorm",
                activation="silu", position="rope")
ENGINE_KW = dict(max_ragged_batch_size=128, max_ragged_sequence_count=4,
                 max_chunk_tokens=32, kv_blocks=64, kv_block_size=8,
                 max_tracked_sequences=32)
SEED = 0

_model = None
_params = None


def tiny_engine(i=0, **cfg_over):
    """Fresh engine over a module-shared model + seeded params — the
    SAME weights every frontend (in-process or subprocess) builds from
    the spec, so cross-frontend parity is byte-meaningful."""
    global _model, _params
    import jax

    from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig

    if _model is None:
        _model = CausalLM(TransformerConfig(**MODEL_KW))
        _params = _model.init(jax.random.PRNGKey(SEED))
    base = dict(ENGINE_KW)
    base.update(cfg_over)
    return InferenceEngineV2(_model, params=_params,
                             config=RaggedInferenceEngineConfig(**base))


def prompts(n, seed, lo=8, hi=24):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, VOCAB, size=int(length)).tolist()
            for length in rng.integers(lo, hi, size=n)]


def run_fleet(fe, ps, max_new, timeout=300):
    hs = [fe.submit(p, max_new_tokens=max_new) for p in ps]
    assert fe.wait_all(hs, timeout=timeout), [h.state for h in hs]
    return [[ev.token for ev in h.drain()] for h in hs]


def local_reference(ps, max_new, n_replicas=1):
    fe = ServingFrontend([tiny_engine(i) for i in range(n_replicas)],
                         ServingConfig(max_queue_depth=64))
    try:
        return run_fleet(fe, ps, max_new)
    finally:
        fe.shutdown(drain=False, timeout=5)


def fed_cfg(peers=(), heartbeat_s=0.3, federation_extra=None, **extra):
    fed = {"enabled": True, "peers": list(peers)}
    fed.update(federation_extra or {})
    return ServingConfig(
        max_queue_depth=64,
        fabric={"enabled": True, "listen": "127.0.0.1:0",
                "heartbeat_s": heartbeat_s, "rpc_timeout_s": 60.0,
                "federation": fed},
        **extra)


def federated_rid(fe):
    return next(r.replica_id for r in fe.router.replicas
                if getattr(r, "is_federated", False))


# ======================================================== peering edges
class TestPeeringEdges:
    def test_self_peering_refused_typed(self):
        fe = ServingFrontend([tiny_engine(0)], fed_cfg())
        try:
            peer = FederationPeer(fe.federation_address, fe.config.fabric,
                                  frontend_id=fe._federation_id,
                                  epoch=fe._federation_epoch + 5)
            with pytest.raises(FederationRefused, match="self_peering"):
                peer.connect()
        finally:
            fe.shutdown(drain=False, timeout=5)

    def test_replica_role_hello_refused(self):
        """The federation listener speaks hello role 'frontend' only —
        a replica-shaped hello (e.g. a misconfigured fabric.peers entry
        pointing at a federation listener) is refused typed."""
        fe = ServingFrontend([tiny_engine(0)], fed_cfg())
        try:
            conn = dial(fe.federation_address, timeout_s=10.0,
                        max_frame_bytes=1 << 20, heartbeat_s=0.3,
                        name="test-bad-role")
            try:
                from deepspeed_tpu.serving.fabric.codec import CODEC_VERSION
                with pytest.raises(FabricError, match="federation_role:"):
                    conn.call("hello", {"codec_version": CODEC_VERSION,
                                        "replica_id": 0, "role": "mixed",
                                        "model_id": "default"},
                              timeout_s=10.0)
            finally:
                conn.close("test done")
        finally:
            fe.shutdown(drain=False, timeout=5)

    def test_stale_epoch_rejected_newer_supersedes(self):
        fe = ServingFrontend([tiny_engine(0)], fed_cfg())
        try:
            fab = fe.config.fabric
            addr = fe.federation_address
            first = FederationPeer(addr, fab, frontend_id="edge-X",
                                   epoch=100)
            first.connect()
            assert first.peer_id == fe._federation_id
            assert len(first.exports) == 1

            stale = FederationPeer(addr, fab, frontend_id="edge-X",
                                   epoch=50)
            with pytest.raises(FederationRefused, match="stale_epoch"):
                stale.connect()
            assert first.alive, "a refused zombie must not hurt the live peer"

            newer = FederationPeer(addr, fab, frontend_id="edge-X",
                                   epoch=200)
            newer.connect()
            deadline = time.monotonic() + 10
            while first.alive and time.monotonic() < deadline:
                time.sleep(0.05)
            assert not first.alive, \
                "a newer epoch must supersede the old connection"
            assert newer.alive
            newer.close()
            first.close()
        finally:
            fe.shutdown(drain=False, timeout=5)

    def test_no_transitive_reexport(self):
        """Adopted capacity is never re-exported: a frontend that itself
        adopted a peer's replica exports only its OWN locals — routing
        loops are impossible by construction."""
        fe_a = ServingFrontend([tiny_engine(0)], fed_cfg())
        fe_b = None
        try:
            fe_b = ServingFrontend([tiny_engine(1)],
                                   fed_cfg(peers=[fe_a.federation_address]))
            assert sum(1 for r in fe_b.router.replicas
                       if getattr(r, "is_federated", False)) == 1
            probe = FederationPeer(fe_b.federation_address,
                                   fe_b.config.fabric,
                                   frontend_id=derive_frontend_id(),
                                   epoch=1)
            probe.connect()
            assert len(probe.exports) == 1, \
                "B must export only its local replica, not A's"
            assert probe.exports[0]["export"] == 0
            probe.close()
        finally:
            if fe_b is not None:
                fe_b.shutdown(drain=False, timeout=5)
            fe_a.shutdown(drain=False, timeout=5)


# ========================================================== shared pool
class TestSharedPool:
    def test_two_frontend_parity_and_observability(self):
        ps = prompts(6, 31)
        ref = local_reference(ps, 8)
        fe_exp = ServingFrontend([tiny_engine(0)], fed_cfg())
        fe_adp = None
        try:
            fe_adp = ServingFrontend(
                [tiny_engine(1)], fed_cfg(peers=[fe_exp.federation_address]))
            got = run_fleet(fe_adp, ps, 8)
            assert got == ref, "federated pool broke greedy parity"
            snap = fe_adp.metrics_snapshot()
            assert snap["requests_federated"] >= 1
            kinds = [e["kind"] for e in fe_exp.journal.events()]
            assert "peer_connected" in kinds
            assert "replica_exported" in kinds
            # the ~1/s observability tick publishes the deduped peer
            # count on BOTH sides (adopter: dialed; exporter: adopted-by)
            deadline = time.monotonic() + 15
            while time.monotonic() < deadline:
                if (fe_adp.metrics_snapshot().get("federation_peers")
                        == 1.0
                        and fe_exp.metrics_snapshot()
                        .get("federation_peers") == 1.0):
                    break
                time.sleep(0.1)
            assert fe_adp.metrics_snapshot()["federation_peers"] == 1.0
            assert fe_exp.metrics_snapshot()["federation_peers"] == 1.0
        finally:
            if fe_adp is not None:
                fe_adp.shutdown(drain=False, timeout=5)
            fe_exp.shutdown(drain=False, timeout=5)

    def test_adopter_without_local_engines(self):
        """An edge frontend with NO local chips serves entirely off the
        shared pool — and the status stream's ``active_total`` reaches
        its capacity probe."""
        ps = prompts(3, 32, lo=8, hi=12)
        ref = local_reference(ps, 40)
        fe_exp = ServingFrontend([tiny_engine(0)], fed_cfg())
        fe_adp = None
        try:
            fe_adp = ServingFrontend(
                [], fed_cfg(peers=[fe_exp.federation_address]))
            handle = fe_adp.router.replica_by_id(federated_rid(fe_adp))
            hs = [fe_adp.submit(p, max_new_tokens=40) for p in ps]
            deadline = time.monotonic() + 60
            seen_total = 0
            while time.monotonic() < deadline and seen_total == 0:
                seen_total = handle._last_active_total
                time.sleep(0.01)
            assert fe_adp.wait_all(hs, timeout=120), [h.state for h in hs]
            got = [[ev.token for ev in h.drain()] for h in hs]
            assert got == ref
            assert seen_total >= 1, \
                "status stream never published the exporter's seat usage"
        finally:
            if fe_adp is not None:
                fe_adp.shutdown(drain=False, timeout=5)
            fe_exp.shutdown(drain=False, timeout=5)

    def test_capacity_accounting(self):
        """The adopter's capacity probe honors the exporter's TOTAL seat
        usage (shared with its local traffic) and the per-peer inflight
        cap."""
        fe_exp = ServingFrontend([tiny_engine(0)], fed_cfg())
        fe_adp = None
        try:
            fe_adp = ServingFrontend(
                [], fed_cfg(peers=[fe_exp.federation_address],
                            federation_extra={"peer_max_inflight": 2}))
            handle = fe_adp.router.replica_by_id(federated_rid(fe_adp))
            assert isinstance(handle, FederatedHandle)
            seats = handle.engine.config.max_ragged_sequence_count
            assert handle.has_capacity
            handle._last_active_total = seats
            assert not handle.has_capacity, \
                "exporter-side saturation must gate the adopter's probe"
            handle._last_active_total = 0
            assert handle.has_capacity

            class _BusyPeer:
                def inflight(self):
                    return 2
            real_peer = handle._peer
            handle._peer = _BusyPeer()
            assert not handle.has_capacity, \
                "peer_max_inflight must cap every mirror from one peer"
            handle._peer = real_peer
            assert handle.has_capacity
        finally:
            if fe_adp is not None:
                fe_adp.shutdown(drain=False, timeout=5)
            fe_exp.shutdown(drain=False, timeout=5)


# ============================================== cross-frontend failover
class TestCrossFrontendFailover:
    def _spawn_frontend(self, tmp_path):
        spec = {"model": MODEL_KW, "engine": ENGINE_KW, "seed": SEED,
                "n_replicas": 1,
                "serving": {"max_queue_depth": 64,
                            "fabric": {"enabled": True,
                                       "listen": "127.0.0.1:0",
                                       "heartbeat_s": 0.3,
                                       "federation": {"enabled": True}}}}
        spec_path = tmp_path / "frontend.json"
        spec_path.write_text(json.dumps(spec))
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        # the peer compiles its own programs: reading them from the run's
        # compile cache (tests/conftest.py) it is through its burst in a
        # few hundred ms, before the kill of the test below can land
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        proc = subprocess.Popen(
            [sys.executable,
             os.path.join(os.path.dirname(__file__), "..", "scripts",
                          "serve_frontend.py"), "--spec", str(spec_path)],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            text=True, env=env)
        line = first_line(proc)
        assert line.startswith("FEDERATION_LISTENING "), line
        return proc, line.split()[1]

    def test_kill_subprocess_frontend_mid_burst(self, tmp_path):
        """The real thing: a peer frontend in its own process, killed
        -9 mid-decode — every in-flight federated stream fails over to
        the adopter's local replica and resumes byte-losslessly."""
        # twelve requests over two replicas of four seats: the peer is
        # still in its burst when the kill lands, however far its tokens
        # run ahead of their frames (a step in flight made a 96-token
        # stream a few hundred ms on the CPU; with four requests the
        # peer's could all be done before the adopter saw two tokens)
        ps = prompts(12, 33, lo=8, hi=12)
        # 4 concurrent seats x (prompt + 96) stays inside the engine's
        # 64x8-token KV pool — 160 here wedges the reference run dry
        ref = local_reference(ps, 96)
        proc, addr = self._spawn_frontend(tmp_path)
        fe = None
        try:
            fe = ServingFrontend(
                [tiny_engine(0)],
                fed_cfg(peers=[addr],
                        fault_tolerance={"enabled": True, "max_retries": 3,
                                         "restart_backoff_s": 0.1}))
            fed_rid = federated_rid(fe)
            hs = [fe.submit(p, max_new_tokens=96) for p in ps]
            deadline = time.monotonic() + 90
            live = False
            while time.monotonic() < deadline and not live:
                live = any(h._req.replica_id == fed_rid
                           and h._req.n_generated >= 2 for h in hs)
                time.sleep(0.002)
            assert live, "no stream ever ran on the federated replica"
            proc.kill()                         # SIGKILL: no goodbye
            assert fe.wait_all(hs, timeout=180), [h.state for h in hs]
            got = [[ev.token for ev in h.drain()] for h in hs]
            snap = fe.metrics_snapshot()
        finally:
            if fe is not None:
                fe.shutdown(drain=False, timeout=5)
            proc.kill()
            proc.wait(timeout=10)
        assert got == ref, "cross-frontend failover broke byte parity"
        assert snap["requests_failed_over"] >= 1


# =========================================================== evacuation
class TestFederatedEvacuation:
    def test_local_drain_onto_peer(self):
        """Removing the adopter's local replica drains its in-flight
        streams onto the PEER's exported replica — the autoscaler's
        drain-onto-peers shutdown path, byte-lossless."""
        ps = prompts(2, 34, lo=8, hi=12)
        ref = local_reference(ps, 160)
        fe_exp = ServingFrontend([tiny_engine(0)], fed_cfg())
        fe_adp = None
        try:
            fe_adp = ServingFrontend(
                [tiny_engine(1)],
                fed_cfg(peers=[fe_exp.federation_address],
                        fault_tolerance={"enabled": True,
                                         "max_retries": 3}))
            local_rid = next(r.replica_id for r in fe_adp.router.replicas
                             if not getattr(r, "is_remote", False))
            hs = [fe_adp.submit(p, max_new_tokens=160) for p in ps]
            deadline = time.monotonic() + 60
            live = False
            while time.monotonic() < deadline and not live:
                live = any(h._req.replica_id == local_rid
                           and h._req.n_generated >= 2 for h in hs)
                time.sleep(0.002)
            assert live, "no stream ever ran on the local replica"
            assert fe_adp.remove_replica(local_rid, timeout_s=30.0)
            assert fe_adp.wait_all(hs, timeout=120), [h.state for h in hs]
            got = [[ev.token for ev in h.drain()] for h in hs]
            snap = fe_adp.metrics_snapshot()
        finally:
            if fe_adp is not None:
                fe_adp.shutdown(drain=False, timeout=5)
            fe_exp.shutdown(drain=False, timeout=5)
        assert got == ref, "drain-onto-peer broke byte parity"
        assert snap["requests_evacuated"] >= 1

    def test_remove_federated_member_requeues_mirrors(self):
        """Removing a FEDERATED member evacuates only the adopter's
        mirrors (the exporter's shared replica keeps serving its own
        traffic) and the mirrors resume locally, byte-lossless."""
        ps = prompts(2, 35, lo=8, hi=12)
        ref = local_reference(ps, 160)
        fe_exp = ServingFrontend([tiny_engine(0)], fed_cfg())
        fe_adp = None
        try:
            fe_adp = ServingFrontend(
                [tiny_engine(1)],
                fed_cfg(peers=[fe_exp.federation_address],
                        fault_tolerance={"enabled": True,
                                         "max_retries": 3}))
            fed_rid = federated_rid(fe_adp)
            hs = [fe_adp.submit(p, max_new_tokens=160) for p in ps]
            deadline = time.monotonic() + 60
            live = False
            while time.monotonic() < deadline and not live:
                live = any(h._req.replica_id == fed_rid
                           and h._req.n_generated >= 2 for h in hs)
                time.sleep(0.002)
            assert live, "no stream ever ran on the federated replica"
            assert fe_adp.remove_replica(fed_rid, timeout_s=30.0)
            assert fe_adp.wait_all(hs, timeout=120), [h.state for h in hs]
            got = [[ev.token for ev in h.drain()] for h in hs]
        finally:
            if fe_adp is not None:
                fe_adp.shutdown(drain=False, timeout=5)
            fe_exp.shutdown(drain=False, timeout=5)
        assert got == ref, "federated-member removal broke byte parity"


# ====================================================== disabled parity
class TestDisabledParity:
    def test_disabled_is_single_frontend_stack(self):
        """``federation`` absent = byte-for-byte the historical fabric
        stack: no identity, no listener, no federation observability."""
        ps = prompts(6, 36)
        plain = ServingFrontend([tiny_engine(0)],
                                ServingConfig(max_queue_depth=64))
        try:
            ref = run_fleet(plain, ps, 8)
        finally:
            plain.shutdown(drain=False, timeout=5)

        fe = ServingFrontend(
            [tiny_engine(1)],
            ServingConfig(max_queue_depth=64, fabric={"enabled": True}))
        try:
            assert fe._federation is None
            assert fe._federation_server is None
            assert fe.federation_address is None
            assert fe._federation_peers == []
            got = run_fleet(fe, ps, 8)
            snap = fe.metrics_snapshot()
            kinds = {e["kind"] for e in fe.journal.events()}
        finally:
            fe.shutdown(drain=False, timeout=5)
        assert got == ref, "disabled federation changed tokens"
        assert snap.get("requests_federated", 0) == 0
        assert not kinds & {"peer_connected", "peer_lost",
                            "replica_exported"}
