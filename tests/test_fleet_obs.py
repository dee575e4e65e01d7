"""Fleet-wide observability plane (ISSUE 19, docs/OBSERVABILITY.md
"Fleet observability").

Unit layer: the tracer's delta-drain cursor, remote-span ingest (id
offsetting, ``remote_parent_id`` re-parenting, clock rebase), the
FleetJournal's exactly-once / schema-refusal / bounded-ring books, the
merged fleet Chrome trace's process→pid / replica→tid mapping, the
flight recorder's role+pid dump stamping and dead-owner sweep, and the
ObsEndpoint HTTP routes over a live frontend.

Integration layer (in-thread replica servers over real TCP, the
test_fabric idiom): a traced fabric fleet must yield ONE stitched
cross-process ``req-<uid>`` chain in the frontend tracer, journal
events from every server source exactly once, and — the counter-reset
satellite — forwarded engine counters that stay monotonic through a
supervisor replica swap (server engine reset) with no negative
windowed deltas.
"""

import json
import os
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

from deepspeed_tpu.serving import ServingConfig, ServingFrontend
from deepspeed_tpu.telemetry import validate_chrome_trace
from deepspeed_tpu.telemetry.fleet import (FleetJournal, ObsEndpoint,
                                           fleet_chrome_trace,
                                           ingest_remote_spans,
                                           source_id_offset)
from deepspeed_tpu.telemetry.flight_recorder import FlightRecorder
from deepspeed_tpu.telemetry.journal import OpsJournal
from deepspeed_tpu.telemetry.tracer import Tracer

from test_fabric import (VOCAB, _Servers, fabric_cfg, local_reference,
                         prompts, run_fleet, tiny_engine)


def _wait(pred, timeout=30.0, interval=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return pred()


# ========================================================== span forwarding
class TestSpanForwarding:
    def test_drain_completed_cursor(self):
        tr = Tracer()
        for i in range(3):
            tr.begin(f"s{i}").end()
        spans, cur = tr.drain_completed(0, limit=2)
        assert [s["name"] for s in spans] == ["s0", "s1"]
        spans, cur = tr.drain_completed(cur, limit=2)
        assert [s["name"] for s in spans] == ["s2"]
        assert tr.drain_completed(cur)[0] == []
        # the hello idiom: a cursor seeded from completed_total skips
        # history entirely
        tr.begin("s3").end()
        assert tr.drain_completed(tr.completed_total)[0] == []

    def test_ingest_offsets_reparents_and_rebases(self):
        tr = Tracer()
        local_parent = 7            # a frontend-local rpc span id
        remote = [
            {"name": "server", "trace_id": "req-1", "span_id": 1,
             "parent_id": None, "t_start": 10.0, "t_end": 10.5,
             "attrs": {"remote_parent_id": local_parent, "replica": 3}},
            {"name": "prefill", "trace_id": "req-1", "span_id": 2,
             "parent_id": 1, "t_start": 10.1, "t_end": 10.2, "attrs": {}},
        ]
        off = source_id_offset(3)
        n = ingest_remote_spans(tr, remote, offset=off, clock_offset_s=0.5,
                                source="replica-3@h", pid=4242)
        assert n == 2
        by_name = {s["name"]: s for s in tr.export()}
        srv, pre = by_name["server"], by_name["prefill"]
        assert srv["span_id"] == 1 + off
        # the cross-process edge: remote_parent_id used VERBATIM
        assert srv["parent_id"] == local_parent
        # remote-local parents shift with their span
        assert pre["parent_id"] == 1 + off
        assert srv["t_start"] == pytest.approx(9.5)
        assert srv["t_end"] == pytest.approx(10.0)
        for s in (srv, pre):
            assert s["attrs"]["source"] == "replica-3@h"
            assert s["attrs"]["pid"] == 4242

    def test_source_offsets_disjoint(self):
        a, b = source_id_offset(0), source_id_offset(1)
        assert a > 0 and b - a >= 2 ** 32


# ============================================================ fleet journal
class TestFleetJournal:
    def _remote_events(self, n=3, source="replica-9@h"):
        j = OpsJournal(source=source)
        for i in range(n):
            j.emit("server_hello", replica=9, role="mixed", reset=bool(i))
        return source, j.events()

    def test_exactly_once_across_replay(self):
        fj = FleetJournal(OpsJournal(source="serving"))
        src, evs = self._remote_events(3)
        assert fj.ingest(src, evs) == (3, 0)
        # reconnect replays the server's ring: all duplicates, none
        # re-ingested, none counted as dropped
        assert fj.ingest(src, evs) == (0, 0)
        book = fj.sources()[src]
        assert book["events"] == 3 and book["last_seq"] == 3
        assert book["duplicates"] == 3 and book["dropped"] == 0
        assert book["remote"] == 1

    def test_schema_invalid_refused_and_counted(self):
        fj = FleetJournal(OpsJournal(source="serving"))
        bad = [{"seq": 1, "t": 0.0, "wall_time": 0.0, "source": "x",
                "kind": "no_such_kind", "detail": {}},
               "not an object"]
        accepted, dropped = fj.ingest("x", bad)
        assert (accepted, dropped) == (0, 2)
        assert fj.sources()["x"]["dropped"] == 2

    def test_merged_view_and_count(self):
        local = OpsJournal(source="serving")
        fj = FleetJournal(local)
        local.emit("obs_listen", address="127.0.0.1:1")
        src, evs = self._remote_events(2)
        fj.ingest(src, evs)
        merged = fj.events()
        assert len(merged) == 3
        assert merged == sorted(merged, key=lambda e: e["wall_time"])
        assert fj.count("server_hello") == 2
        assert fj.count("obs_listen") == 1
        assert fj.events(sources=[src]) == [e for e in merged
                                            if e["source"] == src]

    def test_ring_bounded_per_source(self):
        fj = FleetJournal(OpsJournal(source="serving"),
                          capacity_per_source=4)
        src, evs = self._remote_events(10)
        assert fj.ingest(src, evs) == (10, 0)
        book = fj.sources()[src]
        assert book["events"] == 4 and book["last_seq"] == 10


# ============================================================= chrome trace
class TestFleetChromeTrace:
    def test_pid_tid_mapping_and_validity(self):
        spans = [
            {"name": "queue", "trace_id": "req-1", "span_id": 1,
             "parent_id": None, "t_start": 1.0, "t_end": 1.1, "attrs": {}},
            {"name": "server", "trace_id": "req-1", "span_id": 2,
             "parent_id": 1, "t_start": 1.02, "t_end": 1.08,
             "attrs": {"source": "replica-0@h", "replica": 0}},
            {"name": "server", "trace_id": "req-2", "span_id": 3,
             "parent_id": None, "t_start": 1.2, "t_end": 1.3,
             "attrs": {"source": "replica-1@h", "replica": 1}},
        ]
        trace = fleet_chrome_trace(spans, meta={"phase": "test"})
        assert validate_chrome_trace(trace) == []
        metas = [e for e in trace["traceEvents"] if e["ph"] == "M"]
        pnames = {e["args"]["name"]: e["pid"] for e in metas
                  if e["name"] == "process_name"}
        assert set(pnames) == {"frontend", "replica-0@h", "replica-1@h"}
        assert pnames["frontend"] == 1
        assert len(set(pnames.values())) == 3
        xs = {e["args"]["trace_id"]: e["pid"]
              for e in trace["traceEvents"]
              if e["ph"] == "X" and e["name"] == "server"}
        assert xs["req-1"] == pnames["replica-0@h"]
        assert xs["req-2"] == pnames["replica-1@h"]


# =========================================================== flight recorder
class TestFlightRecorderFleet:
    def test_dump_tag_carries_role_and_pid(self, tmp_path):
        rec = FlightRecorder(Tracer(), dump_dir=str(tmp_path),
                             role="replica-3")
        paths = rec.dump(reason="on_demand")
        for p in paths.values():
            assert os.path.basename(p).endswith(
                f"on_demand_replica-3_{os.getpid()}.json")

    def test_stale_dead_owner_sweep(self, tmp_path):
        proc = subprocess.Popen([sys.executable, "-c", ""])
        proc.wait()
        dead, live = proc.pid, os.getpid()
        (tmp_path / f"flightrec_001_error_replica-0_{dead}.json").write_text(
            "{}")
        (tmp_path / f"trace_001_error_replica-0_{dead}.json").write_text(
            "{}")
        (tmp_path / f"flightrec_001_error_frontend_{live}.json").write_text(
            "{}")
        (tmp_path / "flightrec_unparseable.json").write_text("{}")
        (tmp_path / "unrelated.json").write_text("{}")
        FlightRecorder(Tracer(), dump_dir=str(tmp_path))
        left = sorted(p.name for p in tmp_path.iterdir())
        assert left == ["flightrec_001_error_frontend_%d.json" % live,
                        "flightrec_unparseable.json", "unrelated.json"]


# ============================================================== obs endpoint
class TestObsEndpoint:
    def _get(self, addr, path):
        with urllib.request.urlopen(f"http://{addr}{path}",
                                    timeout=30) as resp:
            return resp.status, resp.read()

    def test_routes_over_live_frontend(self):
        fe = ServingFrontend([tiny_engine()], ServingConfig(
            max_queue_depth=64,
            telemetry={"enabled": True},
            observability={"enabled": True, "listen": "127.0.0.1:0"}))
        try:
            addr = fe.observability_address
            assert addr and addr.rsplit(":", 1)[1] != "0"
            run_fleet(fe, prompts(2, 5), 4)
            status, body = self._get(addr, "/metrics")
            assert status == 200
            assert b"obs_requests" in body and b"requests_completed" in body
            status, body = self._get(addr, "/health")
            health = json.loads(body)
            assert status == 200 and "replicas" in health
            assert health["observability_address"] == addr
            assert "fleet_journal" in health
            status, body = self._get(addr, "/trace")
            trace = json.loads(body)
            assert status == 200
            assert validate_chrome_trace(trace) == []
            assert any(e.get("name") == "decode_step" or e.get("ph")
                       for e in trace["traceEvents"])
            with pytest.raises(urllib.error.HTTPError):
                self._get(addr, "/no_such_route")
            assert fe.metrics_snapshot()["obs_requests"] >= 3
            assert fe.journal.count("obs_listen") == 1
        finally:
            fe.shutdown(drain=False, timeout=5)
        # shutdown closed the listener
        with pytest.raises(OSError):
            self._get(addr, "/metrics")

    def test_fleetctl_status_against_live_endpoint(self, capsys):
        """``scripts/fleetctl.py status`` renders the live endpoint's
        ``/health`` and exits 0; against a closed endpoint it exits 1 (the
        liveness-probe contract of its docstring)."""
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "fleetctl", os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "scripts", "fleetctl.py"))
        fleetctl = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(fleetctl)
        fe = ServingFrontend([tiny_engine()], ServingConfig(
            max_queue_depth=64,
            observability={"enabled": True, "listen": "127.0.0.1:0"}))
        try:
            addr = fe.observability_address
            run_fleet(fe, prompts(2, 5), 4)
            fleetctl.main(["--addr", addr, "status"])
            out = capsys.readouterr().out
            assert "replicas: 1 healthy=1" in out and "queue: depth=" in out
        finally:
            fe.shutdown(drain=False, timeout=5)
        with pytest.raises(SystemExit) as ei:
            fleetctl.main(["--addr", addr, "--timeout", "5", "status"])
        assert ei.value.code == 1

    def test_observability_on_off_absent_greedy_parity(self):
        """Telemetry and the endpoint change what is recorded, never a
        token: enabled, ``enabled: false`` and no block at all serve the
        same greedy streams."""
        ps = prompts(4, 9)
        absent = local_reference(ps, 6)
        assert local_reference(
            ps, 6, telemetry={"enabled": False},
            observability={"enabled": False}) == absent
        assert local_reference(
            ps, 6, telemetry={"enabled": True},
            observability={"enabled": True,
                           "listen": "127.0.0.1:0"}) == absent

    def test_disabled_is_absent(self):
        fe = ServingFrontend([tiny_engine()],
                             ServingConfig(max_queue_depth=64))
        try:
            assert fe.observability_address is None
            assert fe._obs_endpoint is None
        finally:
            fe.shutdown(drain=False, timeout=5)


# ========================================================== fabric end-to-end
class TestFabricTracePropagation:
    def test_cross_process_chain_stitches(self):
        with _Servers(2) as srv:
            fe = ServingFrontend([], fabric_cfg(
                srv.peers, telemetry={"enabled": True}))
            try:
                ps = prompts(4, 11)
                run_fleet(fe, ps, 4)
                # span/journal deltas ride the ~1s status stream
                assert _wait(lambda: fe.metrics_snapshot()
                             ["spans_forwarded"] > 0), \
                    "no spans ever forwarded on the status stream"
                assert _wait(lambda: sum(
                    v.get("remote", 0)
                    for v in fe.fleet.sources().values()) >= 2), \
                    "journal never heard from both servers"
                # a ``server`` span closes with its request, so it rides
                # the tick after the one that brought the first spans
                assert _wait(lambda: any(s["name"] == "server"
                                         for s in fe.tracer.export())), \
                    "no server-side spans in the merged set"
                spans = fe.tracer.export()
                servers = [s for s in spans if s["name"] == "server"]
                ids = {s["span_id"] for s in spans}
                for s in servers:
                    assert str(s["trace_id"]).startswith("req-")
                    assert s["parent_id"] in ids, \
                        "cross-process edge failed to stitch"
                    assert "replica-" in s["attrs"]["source"]
                # every remote source's books balance: exactly-once
                books = fe.fleet.sources()
                remote = {k: v for k, v in books.items() if v["remote"]}
                assert len(remote) == 2
                for book in remote.values():
                    assert book["events"] == book["last_seq"]
                    assert book["dropped"] == 0
                report = fe.health_report()
                assert len(report["remotes"]) == 2
                for r in report["remotes"]:
                    assert r["connected"]
                    assert isinstance(r["clock_offset_s"], float)
                assert validate_chrome_trace(
                    fleet_chrome_trace(spans)) == []
            finally:
                fe.shutdown(drain=False, timeout=5)

    def test_counter_reset_detection_across_replica_swap(self):
        """Satellite: forwarded engine counters must stay monotonic
        through a supervisor replica swap (transport loss -> re-dial ->
        server-side engine reset restarts the server's cumulative
        counters from zero) — the frontend's cumulative view never goes
        backwards and the windowed deltas spanning the swap never go
        negative."""
        from deepspeed_tpu.serving.fabric.remote import RemoteHandle

        rng = np.random.default_rng(3)
        head = rng.integers(0, VOCAB, size=16).tolist()
        mk = lambda seed: [head + rng.integers(0, VOCAB, size=6).tolist()
                           for _ in range(3)]
        # the SERVER owns its engine's config: prefix caching must be
        # enabled there for hits to exist server-side at all
        srv_cfg = ServingConfig(prefix_cache={"enabled": True})
        with _Servers(1, server_config=srv_cfg, heartbeat_s=0.2) as srv:
            fe = ServingFrontend([], fabric_cfg(
                srv.peers, heartbeat_s=0.2,
                fault_tolerance={"enabled": True, "max_retries": 3,
                                 "restart_backoff_s": 0.05,
                                 "max_restarts_in_window": 10}))
            watched = RemoteHandle._FORWARDED_COUNTERS
            try:
                run_fleet(fe, mk(1), 4)   # first wave primes the cache
                run_fleet(fe, mk(1), 4)   # second wave hits the head
                assert _wait(lambda: fe.metrics_snapshot()
                             ["prefix_blocks_hit"] > 0), \
                    "shared-prefix traffic never forwarded a hit counter"
                fe.windowed.tick()
                before = fe.metrics_snapshot()
                # sever the transport: the supervisor re-dials with a
                # server-side reset — a FRESH engine whose cumulative
                # counters restart from zero
                handle = next(r for r in fe.router.replicas
                              if isinstance(r, RemoteHandle))
                handle._conn.close("injected transport loss")
                assert _wait(lambda: fe.journal.count(
                    "replica_reconnected") > 0), "supervisor never re-dialed"
                run_fleet(fe, mk(2), 4)
                assert _wait(lambda: fe.metrics_snapshot()
                             ["prefix_blocks_hit"]
                             > before["prefix_blocks_hit"]), \
                    "post-swap traffic never moved the forwarded counter"
                fe.windowed.tick()
                after = fe.metrics_snapshot()
                for name in watched:
                    assert after.get(name, 0.0) >= before.get(name, 0.0), \
                        f"{name} went backwards across the replica swap"
                    delta = fe.windowed.window_delta(name, 3600.0)
                    assert delta >= 0.0, \
                        f"{name} produced a negative windowed delta"
                # the reset-detection branch itself: a status frame whose
                # counters are BELOW the high-water mark (server engine
                # reset) re-bases instead of subtracting into a phantom
                new_handle = next(r for r in fe.router.replicas
                                  if isinstance(r, RemoteHandle))
                base = fe.metrics_snapshot()["prefix_blocks_hit"]
                new_handle._counters_last["prefix_blocks_hit"] = 10 ** 9
                new_handle._ev_status(
                    {"counters": {"prefix_blocks_hit": 2.0}})
                got = fe.metrics_snapshot()["prefix_blocks_hit"]
                assert got == pytest.approx(base + 2.0), \
                    "reset epoch was not re-based from zero"
            finally:
                fe.shutdown(drain=False, timeout=5)
