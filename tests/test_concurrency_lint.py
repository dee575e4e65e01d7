"""Concurrency lint + RankedLock runtime tests (docs/CONCURRENCY.md).

Four layers:

- **Fixture snippets** (seeded mutations): each static check class —
  guarded miss, helper indirection (one level AND chained), writes-only
  mode, rank inversion, lock cycle, blocking-while-locked (direct and
  one call level deep), declared-name audits, stale/unjustified
  baseline — demonstrated on minimal sources the analyzer must flag (or
  must NOT flag, for the legal patterns).
- **Whole-repo gate**: ``run_repo(REPO)`` returns zero non-baselined
  findings — the same invariant ``scripts/lint_concurrency.py`` gates
  tier-1 on.
- **Regression tests** for the real findings this lint surfaced and
  fixed (queue brownout/preempt-pressure fields, flight-recorder
  cadence watermark), pinned by baseline id so the fix can't silently
  regress, plus racing-thread behavioral checks.
- **RankedLock runtime**: order enforcement, reentrancy, condition
  wait, hold-time histogram, debug-off allocation-freedom (tracemalloc)
  and the declaration audits (LOCK_RANKS vs constructions vs the
  docs/CONCURRENCY.md rank table, all both ways) — ending in a chaos
  run (supervisor kill + autoscaler churn) under debug mode asserting
  no ordering violations and no over-threshold holds.
"""

import os
import re
import threading
import time
import tracemalloc

import numpy as np
import pytest

from deepspeed_tpu.analysis import (analyze_source, apply_baseline,
                                    parse_baseline, render_baseline,
                                    run_repo)
from deepspeed_tpu.analysis.concurrency import analyze as analyze_repo
from deepspeed_tpu.analysis.declared import (_template_matches_const,
                                             _template_of,
                                             check_declared_names)
from deepspeed_tpu.utils import locks as locks_mod
from deepspeed_tpu.utils.locks import (LOCK_RANKS, LockOrderError,
                                       RankedCondition, RankedLock,
                                       disable_lock_debug,
                                       enable_lock_debug, lock_debug)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _ids(findings, check=None):
    return sorted(f.baseline_id for f in findings
                  if check is None or f.check == check)


# ------------------------------------------------------ guarded fields
class TestGuardedFields:
    def test_unguarded_read_and_write_flagged(self):
        src = """
import threading

class C:
    _GUARDED_BY = {"_inflight": "_lock"}

    def __init__(self):
        self._lock = threading.Lock()
        self._inflight = 0          # init is exempt

    def bad_read(self):
        return self._inflight

    def bad_write(self):
        self._inflight += 1

    def good(self):
        with self._lock:
            self._inflight += 1
"""
        found = analyze_source(src)
        ids = _ids(found, "guarded-field")
        assert any("C.bad_read:_inflight" in i for i in ids)
        assert any("C.bad_write:_inflight" in i for i in ids)
        assert not any("C.good" in i for i in ids)
        assert not any("C.__init__" in i for i in ids)

    def test_helper_indirection_one_level_and_chained(self):
        src = """
import threading

class C:
    _GUARDED_BY = {"_n": "_lock"}

    def __init__(self):
        self._lock = threading.Lock()
        self._n = 0

    def _bump_locked(self):
        self._n += 1                 # every call site holds the lock

    def _note_locked(self):
        self._n += 1                 # called only via _bump2_locked

    def _bump2_locked(self):
        self._note_locked()          # chained helper, still guarded

    def public(self):
        with self._lock:
            self._bump_locked()
            self._bump2_locked()
"""
        assert _ids(analyze_source(src), "guarded-field") == []

    def test_helper_with_one_unlocked_call_site_flagged(self):
        src = """
import threading

class C:
    _GUARDED_BY = {"_n": "_lock"}

    def __init__(self):
        self._lock = threading.Lock()
        self._n = 0

    def _bump(self):
        self._n += 1

    def locked_path(self):
        with self._lock:
            self._bump()

    def unlocked_path(self):
        self._bump()                 # poisons the caller-holds claim
"""
        ids = _ids(analyze_source(src), "guarded-field")
        assert any("C._bump:_n" in i for i in ids)

    def test_public_helper_is_an_entry_point(self):
        src = """
import threading

class C:
    _GUARDED_BY = {"_n": "_lock"}

    def __init__(self):
        self._lock = threading.Lock()
        self._n = 0

    def bump(self):
        self._n += 1                 # public: must guard internally

    def caller(self):
        with self._lock:
            self.bump()
"""
        ids = _ids(analyze_source(src), "guarded-field")
        assert any("C.bump:_n" in i for i in ids)

    def test_writes_only_mode(self):
        src = """
import threading

class C:
    _GUARDED_BY = {"flag": "_lock:writes"}

    def __init__(self):
        self._lock = threading.Lock()
        self.flag = False

    def read_free(self):
        return self.flag             # reads are lock-free by contract

    def bad_write(self):
        self.flag = True

    def good_write(self):
        with self._lock:
            self.flag = True
"""
        ids = _ids(analyze_source(src), "guarded-field")
        assert any("C.bad_write:flag" in i for i in ids)
        assert not any("C.read_free" in i for i in ids)
        assert not any("C.good_write" in i for i in ids)

    def test_guarded_by_trailing_comment(self):
        src = """
import threading

class C:
    def __init__(self):
        self._lock = threading.Lock()
        self._items = []  # guarded-by: _lock

    def bad(self):
        return len(self._items)
"""
        ids = _ids(analyze_source(src), "guarded-field")
        assert any("C.bad:_items" in i for i in ids)


# ----------------------------------------------------------- lock order
class TestLockOrder:
    def test_rank_inversion_flagged(self):
        src = """
from deepspeed_tpu.utils.locks import RankedLock

class C:
    def __init__(self):
        self._outer = RankedLock("telemetry.tracer")
        self._inner = RankedLock("serving.queue")

    def bad(self):
        with self._outer:
            with self._inner:
                pass
"""
        found = analyze_source(src)
        ids = _ids(found, "lock-order")
        assert any("telemetry.tracer->serving.queue" in i for i in ids)

    def test_correct_order_clean(self):
        src = """
from deepspeed_tpu.utils.locks import RankedLock

class C:
    def __init__(self):
        self._outer = RankedLock("serving.queue")
        self._inner = RankedLock("telemetry.tracer")

    def good(self):
        with self._outer:
            with self._inner:
                pass
"""
        found = analyze_source(src)
        assert _ids(found, "lock-order") == []
        assert _ids(found, "lock-cycle") == []

    def test_cross_object_cycle_detected(self):
        src = """
import threading

class A:
    def __init__(self, b: "B"):
        self._lock = threading.Lock()
        self.b = b

    def helper_a(self):
        with self._lock:
            pass

    def step(self):
        with self._lock:
            self.b.helper_b()

class B:
    def __init__(self, a: A):
        self._lock = threading.Lock()
        self.a = a

    def helper_b(self):
        with self._lock:
            pass

    def step_back(self):
        with self._lock:
            self.a.helper_a()
"""
        found = analyze_source(src)
        cyc = _ids(found, "lock-cycle")
        assert len(cyc) == 1
        assert "A._lock" in cyc[0] and "B._lock" in cyc[0]

    def test_peer_instance_same_lock_nesting_flagged(self):
        """Two instances of one class taking each other's equally-named
        lock is the classic unordered AB-BA deadlock — it must surface
        as a self-loop cycle (unranked) instead of being skipped as
        'same lock id' (post-review fix)."""
        src = """
import threading

class R:
    def __init__(self, peer: "R"):
        self._lock = threading.Lock()
        self.peer = peer

    def grab_peer_side(self):
        with self._lock:
            pass

    def merge(self):
        with self._lock:
            self.peer.grab_peer_side()
"""
        cyc = _ids(analyze_source(src), "lock-cycle")
        assert cyc and "R._lock" in cyc[0]

    def test_equal_rank_peer_edge_fails_rank_check(self):
        src = """
from deepspeed_tpu.utils.locks import RankedLock

class Rep:
    def __init__(self, peer: "Rep"):
        self._lock = RankedLock("serving.replica")
        self.peer = peer

    def grab_peer_side(self):
        with self._lock:
            pass

    def merge(self):
        with self._lock:
            self.peer.grab_peer_side()
"""
        ids = _ids(analyze_source(src), "lock-order")
        assert any("serving.replica->serving.replica" in i for i in ids)

    def test_reentrant_same_attr_nesting_allowed(self):
        src = """
from deepspeed_tpu.utils.locks import RankedLock

class M:
    def __init__(self):
        self._lock = RankedLock("serving.router.membership",
                                reentrant=True)

    def _inner(self):
        with self._lock:
            pass

    def outer(self):
        with self._lock:
            with self._lock:      # same-object RLock re-entry: legal
                pass
            self._inner()         # self-call re-entry: legal too
"""
        found = analyze_source(src)
        assert _ids(found, "lock-order") == []
        assert _ids(found, "lock-cycle") == []

    def test_rank_check_via_call_resolution(self):
        src = """
from deepspeed_tpu.utils.locks import RankedLock

class Inner:
    def __init__(self):
        self._lock = RankedLock("serving.queue")

    def grab_inner_lock(self):
        with self._lock:
            pass

class Outer:
    def __init__(self):
        self._lock = RankedLock("serving.replica")
        self.inner = Inner()

    def bad(self):
        with self._lock:                 # rank 70
            self.inner.grab_inner_lock()   # rank 60: inversion
"""
        ids = _ids(analyze_source(src), "lock-order")
        assert any("serving.replica->serving.queue" in i for i in ids)

    def test_cross_object_lexical_nesting_flagged(self):
        """Post-review fix: `with self._lock: with replica._lock:` —
        lexically nested acquisition of ANOTHER object's lock, typed by
        a parameter annotation or a constructor-typed attribute — joins
        the order graph instead of being invisible."""
        src = """
from deepspeed_tpu.utils.locks import RankedLock

class Rep:
    def __init__(self):
        self._lock = RankedLock("serving.queue")

class Router:
    def __init__(self):
        self._lock = RankedLock("serving.replica")
        self.rep = Rep()

    def via_attr(self):
        with self._lock:                  # rank 70
            with self.rep._lock:          # rank 60: inversion
                pass

    def via_param(self, r: Rep):
        with self._lock:
            with r._lock:
                pass
"""
        ids = _ids(analyze_source(src), "lock-order")
        assert sum("serving.replica->serving.queue" in i
                   for i in ids) == 2

    def test_escaped_method_reference_grounds_helper_cycle(self):
        """Post-review fix: a helper whose reference escapes (callback
        wiring) is an entry point — a closed helper-call cycle must not
        keep the optimistic caller-holds seed."""
        src = """
import threading

class C:
    _GUARDED_BY = {"_n": "_lock"}

    def __init__(self):
        self._lock = threading.Lock()
        self._n = 0
        self.cb = self._a          # escapes: may run lock-free

    def _a(self):
        self._n += 1
        self._b()

    def _b(self):
        self._a()
"""
        ids = _ids(analyze_source(src), "guarded-field")
        assert any("C._a:_n" in i for i in ids)


# ------------------------------------------------- blocking while locked
class TestBlockingWhileLocked:
    def test_direct_blocking_ops_flagged(self):
        src = """
import threading
import time

class C:
    def __init__(self):
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self.thread = threading.Thread(target=lambda: None)

    def sleepy(self):
        with self._lock:
            time.sleep(1)

    def joiny(self):
        with self._lock:
            self.thread.join(1)

    def waity(self):
        with self._lock:
            self._stop.wait(1)

    def io(self):
        with self._lock:
            open("/tmp/x")
"""
        ids = _ids(analyze_source(src), "blocking-while-locked")
        assert any("C.sleepy:time.sleep" in i for i in ids)
        assert any("C.joiny:join" in i for i in ids)
        assert any("C.waity:wait" in i for i in ids)
        assert any("C.io:open" in i for i in ids)

    def test_condition_wait_on_held_lock_allowed(self):
        src = """
import threading

class C:
    def __init__(self):
        self._lock = threading.Condition()

    def pop(self):
        with self._lock:
            self._lock.wait(0.1)     # releases while waiting: legal
"""
        assert _ids(analyze_source(src), "blocking-while-locked") == []

    def test_one_level_call_indirection(self):
        src = """
import threading
import time

class C:
    def __init__(self):
        self._lock = threading.Lock()

    def _drain(self):
        time.sleep(0.1)              # no lock held HERE

    def admin(self):
        with self._lock:
            self._drain()            # ...but held at the call site
"""
        found = analyze_source(src)
        ids = _ids(found, "blocking-while-locked")
        # the stable token is the CALLEE name alone (the op list depends
        # on which unique-name candidates exist elsewhere; the baseline
        # id must survive unrelated file additions) — the op still
        # appears in the human-facing detail
        assert any(i.endswith("C.admin:_drain") for i in ids)
        detail = next(f.detail for f in found
                      if f.baseline_id.endswith("C.admin:_drain"))
        assert "time.sleep" in detail


# ------------------------------------------------------------- baseline
class TestBaseline:
    GOOD = (
        "[[finding]]\n"
        'id = "guarded-field:a.py:C.m:_x"\n'
        'justification = "audited: single-writer by construction"\n'
    )

    def _finding(self):
        from deepspeed_tpu.analysis import Finding

        return Finding("guarded-field", "a.py", 3, "C.m", "_x", "read")

    def test_suppression_and_stale_detection(self):
        entries, problems = parse_baseline(self.GOOD)
        assert problems == []
        active, suppressed = apply_baseline([self._finding()], entries)
        assert active == [] and len(suppressed) == 1
        # same baseline, no findings -> the entry is stale = an error
        active, suppressed = apply_baseline([], entries)
        assert [f.check for f in active] == ["stale-baseline"]
        assert suppressed == []

    def test_missing_justification_is_an_error(self):
        text = ('[[finding]]\n'
                'id = "guarded-field:a.py:C.m:_x"\n'
                'justification = ""\n')
        _, problems = parse_baseline(text)
        assert [p.check for p in problems] == ["baseline-unjustified"]

    def test_scoped_run_reports_no_stale_entries(self):
        """A path-scoped run cannot tell 'healed' from 'out of scope':
        baseline entries for files outside the analyzed paths must NOT
        be reported as stale (post-review fix — following the stale
        advice would delete audited justifications)."""
        active, _ = run_repo(REPO, paths=["deepspeed_tpu/telemetry"])
        assert [f for f in active if f.check == "stale-baseline"] == []

    def test_update_baseline_refuses_scoped_paths(self):
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "lint_cli", os.path.join(REPO, "scripts",
                                     "lint_concurrency.py"))
        cli = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cli)
        rc = cli.main(["--update-baseline", "deepspeed_tpu/telemetry"])
        assert rc == 2
        # the audited baseline was not touched
        entries, problems = parse_baseline(
            open(os.path.join(REPO,
                              "deepspeed_tpu/analysis/baseline.toml")).read())
        assert problems == [] and len(entries) == 8
        assert not any("UNAUDITED" in e.justification for e in entries)

    def test_render_preserves_justifications(self):
        entries, _ = parse_baseline(self.GOOD)
        text = render_baseline([self._finding()], entries)
        assert "audited: single-writer by construction" in text
        # a new finding gets a visible UNAUDITED placeholder
        from deepspeed_tpu.analysis import Finding

        new = Finding("lock-order", "b.py", 1, "D.n", "x->y", "inversion")
        text = render_baseline([self._finding(), new], entries)
        assert "UNAUDITED" in text
        reparsed, problems = parse_baseline(text)
        assert problems == [] and len(reparsed) == 2


# ------------------------------------------------------- declared names
class TestDeclaredNames:
    def test_template_matching(self):
        import ast

        tpl = _template_of(ast.parse('f"ttft_s_class_{c}"',
                                     mode="eval").body)
        assert _template_matches_const(tpl, "ttft_s_class_interactive")
        assert not _template_matches_const(tpl, "tpot_s_class_interactive")
        assert not _template_matches_const(tpl, "ttft_s_class_")

    def _mini_repo(self, tmp_path, app_src):
        pkg = tmp_path / "deepspeed_tpu"
        for sub in ("utils", "serving", "telemetry", "analysis"):
            (pkg / sub).mkdir(parents=True)
            (pkg / sub / "__init__.py").write_text("")
        (pkg / "__init__.py").write_text("")
        (pkg / "utils" / "locks.py").write_text(
            'LOCK_RANKS = {"a.outer": 10, "a.inner": 20}\n')
        (pkg / "serving" / "metrics.py").write_text(
            "def serving_metrics(classes=(\"interactive\",)):\n"
            "    reg = object()\n"
            "    for c in (\"requests_total\", \"tokens_total\"):\n"
            "        reg.counter(c)\n"
            "    reg.gauge(\"depth\")\n"
            "    for cls in classes:\n"
            "        reg.histogram(f\"lat_s_class_{cls}\")\n")
        (pkg / "telemetry" / "journal.py").write_text(
            "EVENT_SCHEMAS = {\"thing_happened\": frozenset({\"x\"})}\n")
        # the real declared-metrics extractor also reads slo.py's
        # AlertEngine.__init__ declaring scope — provide an empty one
        (pkg / "telemetry" / "slo.py").write_text(
            "class AlertEngine:\n"
            "    def __init__(self):\n"
            "        pass\n")
        (pkg / "serving" / "app.py").write_text(app_src)
        return str(tmp_path)

    def test_clean_usage_passes(self, tmp_path):
        root = self._mini_repo(tmp_path, (
            "class App:\n"
            "    def ok(self, m, cls):\n"
            "        m.counter(\"requests_total\").inc()\n"
            "        m.histogram(f\"lat_s_class_{cls}\")\n"
            "        self.journal.emit(\"thing_happened\", x=1)\n"))
        assert check_declared_names(root) == []

    def test_seeded_mutations_caught(self, tmp_path):
        root = self._mini_repo(tmp_path, (
            "class App:\n"
            "    def bad(self, m):\n"
            "        m.counter(\"bogus_counter\").inc()\n"
            "        m.gauge(f\"depth_of_{self.x}\")\n"
            "        self.journal.emit(\"unknown_kind\", x=1)\n"))
        found = check_declared_names(root)
        ids = sorted(f.baseline_id for f in found)
        assert any("metric-name" in i and "bogus_counter" in i
                   for i in ids)
        assert any("metric-name" in i and "depth_of_" in i for i in ids)
        assert any("journal-kind" in i and "unknown_kind" in i
                   for i in ids)

    def test_module_level_and_nested_scopes_covered(self, tmp_path):
        """Post-review fix: metric/journal calls at MODULE scope (import
        -time registry wiring) and inside nested classes are audited
        too, not just top-level method bodies."""
        root = self._mini_repo(tmp_path, (
            "REG = object()\n"
            "REG.counter(\"module_scope_bogus\").inc()\n"
            "def outer():\n"
            "    class Inner:\n"
            "        def bad(self, m):\n"
            "            m.gauge(\"nested_scope_bogus\")\n"))
        ids = sorted(f.baseline_id for f in check_declared_names(root))
        assert any("module_scope_bogus" in i for i in ids)
        assert any("nested_scope_bogus" in i for i in ids)

    def test_journal_kind_param_propagation(self, tmp_path):
        root = self._mini_repo(tmp_path, (
            "class App:\n"
            "    def _record(self, action):\n"
            "        self.journal.emit(action, x=1)\n"
            "    def go(self):\n"
            "        self._record(\"thing_happened\")\n"
            "    def go_bad(self):\n"
            "        self._record(\"nope\")\n"))
        found = check_declared_names(root)
        ids = sorted(f.baseline_id for f in found)
        assert any("journal-kind" in i and ":nope" in i for i in ids)
        assert not any(":thing_happened" in i for i in ids)


# --------------------------------------------------------- whole repo
class TestWholeRepo:
    def test_repo_is_clean_modulo_baseline(self):
        active, suppressed = run_repo(REPO)
        assert active == [], "\n".join(f.render() for f in active)
        # the baseline is small and justified, not a dumping ground
        assert len(suppressed) <= 12

    # regression pins for the real findings this lint surfaced and
    # fixed (ISSUE 14 satellite): the ids must stay absent from the RAW
    # (un-baselined) findings — reintroducing the unlocked access would
    # resurface them and fail both this test and the tier-1 gate.
    FIXED_IDS = (
        "guarded-field:deepspeed_tpu/serving/queue.py:"
        "AdmissionQueue.set_preempt_pressure:_preempt_pressure",
        "guarded-field:deepspeed_tpu/serving/queue.py:"
        "AdmissionQueue.set_healthy_fraction:_proactive_frac",
        "guarded-field:deepspeed_tpu/serving/queue.py:"
        "AdmissionQueue.set_healthy_fraction:_healthy_frac",
        "guarded-field:deepspeed_tpu/telemetry/flight_recorder.py:"
        "FlightRecorder.maybe_snapshot:_last_snapshot_t",
    )

    def test_fixed_findings_stay_fixed(self):
        raw = analyze_repo(REPO)
        raw_ids = {f.baseline_id for f in raw}
        for fixed in self.FIXED_IDS:
            assert fixed not in raw_ids, fixed

    def test_fixed_finding_shapes_are_detectable(self):
        """The pre-fix code shapes, as fixtures: proves the whole-repo
        green isn't vacuous — the analyzer catches exactly what was
        fixed."""
        pre_fix_queue = """
import threading

class AdmissionQueue:
    _GUARDED_BY = {"_preempt_pressure": "_lock:writes",
                   "_healthy_frac": "_lock"}

    def __init__(self):
        self._lock = threading.Condition()
        self._preempt_pressure = False
        self._healthy_frac = 1.0

    def set_preempt_pressure(self, active):
        self._preempt_pressure = bool(active)     # the PR-11 shape

    def set_healthy_fraction(self, frac):
        with self._lock:
            self._healthy_frac = frac
        return round(self._healthy_frac, 4)       # re-read after release
"""
        ids = _ids(analyze_source(pre_fix_queue), "guarded-field")
        assert any("set_preempt_pressure:_preempt_pressure" in i
                   for i in ids)
        assert any("set_healthy_fraction:_healthy_frac" in i for i in ids)

    # behavioral regression: racing writers/readers over the fixed
    # fields — the journal transition must carry the fraction that
    # caused it and the flag write must not tear shed accounting
    def test_queue_pressure_flag_race(self):
        from deepspeed_tpu.serving.metrics import serving_metrics
        from deepspeed_tpu.serving.queue import AdmissionQueue
        from deepspeed_tpu.serving.request import Rejected, ServingRequest

        q = AdmissionQueue(2, serving_metrics(), brownout_threshold=0.0)
        stop = threading.Event()

        def flip():
            while not stop.is_set():
                q.set_preempt_pressure(True)
                q.set_preempt_pressure(False)

        t = threading.Thread(target=flip, daemon=True)
        t.start()
        try:
            shed = 0
            for i in range(200):
                req = ServingRequest([1, 2], 4, 1, None, None)
                try:
                    q.offer(req)
                except Rejected:
                    shed += 1
            assert shed == 198          # depth 2: everything else sheds
        finally:
            stop.set()
            t.join(1)

    def test_brownout_journal_fraction_consistent_under_race(self):
        from deepspeed_tpu.telemetry.journal import OpsJournal
        from deepspeed_tpu.serving.queue import AdmissionQueue

        journal = OpsJournal(capacity=4096)
        q = AdmissionQueue(8, None, brownout_threshold=0.5,
                           journal=journal)
        values = [0.1, 0.2, 0.3, 0.4, 0.9, 1.0]

        def hammer(seed):
            rng = np.random.default_rng(seed)
            for _ in range(200):
                q.set_healthy_fraction(float(rng.choice(values)))

        threads = [threading.Thread(target=hammer, args=(s,))
                   for s in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(5)
        # every enter/exit event must carry one of the fractions a
        # caller actually set — the pre-fix re-read-after-release could
        # smuggle a concurrent writer's value into the record
        for ev in journal.events():
            assert ev["detail"]["healthy_fraction"] in values


# ------------------------------------------------------ RankedLock unit
@pytest.fixture
def debug_state():
    state = enable_lock_debug(hold_threshold_s=60.0)
    try:
        yield state
    finally:
        disable_lock_debug()


class TestRankedLock:
    def test_undeclared_name_fails_fast(self):
        with pytest.raises(KeyError):
            RankedLock("no.such.lock")

    def test_order_enforced_in_debug_mode(self, debug_state):
        outer = RankedLock("serving.queue")        # 60
        inner = RankedLock("telemetry.tracer")     # 160
        with outer:
            with inner:                            # ascending: fine
                pass
        with pytest.raises(LockOrderError):
            with inner:
                with outer:                        # descending: violation
                    pass
        assert len(debug_state.violations) == 1
        v = debug_state.violations[0]
        assert v["lock"] == "serving.queue"
        assert v["holding"] == ["telemetry.tracer"]

    def test_self_deadlock_detected(self, debug_state):
        lock = RankedLock("serving.replica")
        with lock:
            with pytest.raises(LockOrderError, match="self-deadlock"):
                lock.acquire()

    def test_reentrant_reacquire_allowed(self, debug_state):
        rl = RankedLock("serving.router.membership", reentrant=True)
        with rl:
            with rl:
                pass
        assert debug_state.violations == []

    def test_condition_wait_and_notify(self, debug_state):
        cond = RankedCondition("serving.queue")
        hits = []

        def waiter():
            with cond:
                while not hits:
                    if not cond.wait(2.0):
                        return
            hits.append("woke")

        t = threading.Thread(target=waiter)
        t.start()
        time.sleep(0.05)
        with cond:
            hits.append("set")
            cond.notify_all()
        t.join(3)
        assert hits == ["set", "woke"]
        assert debug_state.violations == []

    def test_hold_histogram_and_over_threshold(self):
        from deepspeed_tpu.serving.metrics import serving_metrics

        reg = serving_metrics()
        state = enable_lock_debug(metrics=reg, hold_threshold_s=0.02)
        try:
            lock = RankedLock("serving.handoff")
            with lock:
                pass
            with lock:
                time.sleep(0.05)            # over the 20ms threshold
        finally:
            disable_lock_debug()
        hist = reg.histogram("lock_hold_s")
        assert hist.count >= 2
        assert len(state.over_holds) == 1
        assert state.over_holds[0]["lock"] == "serving.handoff"
        assert state.over_holds[0]["hold_s"] >= 0.02

    def test_over_hold_of_recorders_own_lock_does_not_deadlock(self):
        """Post-review fix: hold-time side effects (including the
        over-hold flight-recorder dump, which takes the recorder's own
        ranked lock) must run AFTER the real release — an over-threshold
        hold of `telemetry.recorder` itself used to self-deadlock the
        releasing thread inside release()."""
        from deepspeed_tpu.telemetry.flight_recorder import FlightRecorder
        from deepspeed_tpu.telemetry.tracer import Tracer

        recorder = FlightRecorder(Tracer(enabled=True))
        state = enable_lock_debug(recorder=recorder,
                                  hold_threshold_s=0.01)
        try:
            done = threading.Event()

            def hold_and_release():
                with recorder._lock:        # the recorder's OWN lock
                    time.sleep(0.05)        # over the 10ms threshold
                done.set()

            t = threading.Thread(target=hold_and_release, daemon=True)
            t.start()
            assert done.wait(5.0), \
                "release() deadlocked dumping its own over-hold"
            assert any(r["lock"] == "telemetry.recorder"
                       for r in state.over_holds)
        finally:
            disable_lock_debug()

    def test_maybe_snapshot_claims_watermark_atomically(self):
        """Post-review fix: the cadence check claims the watermark in
        the same locked section it reads it — a racer arriving before
        the (possibly slow) snapshot completes must skip."""
        from deepspeed_tpu.telemetry.flight_recorder import FlightRecorder
        from deepspeed_tpu.telemetry.tracer import Tracer

        fr = FlightRecorder(Tracer(enabled=True))
        calls = []
        fr.snapshot_metrics = lambda: calls.append(1)   # never advances
        fr.maybe_snapshot(interval_s=60.0)
        fr.maybe_snapshot(interval_s=60.0)   # pre-fix: ran again
        assert calls == [1]

    def test_disabled_path_allocation_free(self):
        assert lock_debug() is None
        lock = RankedLock("serving.replica")
        with lock:                          # warm any lazy state
            pass
        here = __file__
        locks_file = RankedLock.acquire.__code__.co_filename
        tracemalloc.start()
        try:
            before = tracemalloc.take_snapshot()
            for _ in range(2000):
                with lock:
                    pass
                lock.acquire()
                lock.release()
            after = tracemalloc.take_snapshot()
        finally:
            tracemalloc.stop()
        leaked = sum(
            st.count_diff for st in after.compare_to(before, "lineno")
            if st.traceback and st.traceback[0].filename in (here,
                                                             locks_file)
            and st.count_diff > 0)
        assert leaked <= 8, (
            f"disabled RankedLock leaked {leaked} objects over 4000 "
            "acquire/release cycles")


# ------------------------------------------------- declaration audits
class TestDeclarationAudits:
    def _used_rank_names(self):
        from deepspeed_tpu.analysis.concurrency import build_model

        model = build_model(REPO)
        used = set()
        for cm in model.classes:
            for decl in cm.locks.values():
                if decl.rank_name:
                    used.add(decl.rank_name)
            used.update(cm.rank_hints.values())
        return used

    def test_lock_ranks_and_constructions_agree_both_ways(self):
        used = self._used_rank_names()
        undeclared = used - set(LOCK_RANKS)
        assert not undeclared, f"locks constructed with undeclared " \
                               f"rank names: {sorted(undeclared)}"
        unused = set(LOCK_RANKS) - used
        assert not unused, f"LOCK_RANKS entries no lock uses: " \
                           f"{sorted(unused)}"

    def test_docs_rank_table_matches_lock_ranks_both_ways(self):
        path = os.path.join(REPO, "docs", "CONCURRENCY.md")
        doc = open(path).read()
        rows = dict(
            (m.group(2), int(m.group(1)))
            for m in re.finditer(r"^\| (\d+) \| `([\w.]+)` \|", doc,
                                 re.MULTILINE))
        assert rows == LOCK_RANKS, (
            "docs/CONCURRENCY.md rank table drifted from LOCK_RANKS:\n"
            f"doc-only: {sorted(set(rows) - set(LOCK_RANKS))}\n"
            f"code-only: {sorted(set(LOCK_RANKS) - set(rows))}\n"
            f"value diffs: "
            f"{ {k: (rows[k], LOCK_RANKS[k]) for k in rows if k in LOCK_RANKS and rows[k] != LOCK_RANKS[k]} }")

    def test_ranks_are_unique_and_runtime_matches_static(self):
        assert len(set(LOCK_RANKS.values())) == len(LOCK_RANKS)
        from deepspeed_tpu.analysis.concurrency import parse_lock_ranks

        assert parse_lock_ranks(REPO) == LOCK_RANKS


# --------------------------------------------------------------- chaos
VOCAB = 128
_model = None
_params = None


def _tiny_engine(i=0, kv_blocks=64, max_seqs=4):
    global _model, _params
    from deepspeed_tpu.inference.v2.engine_v2 import (
        InferenceEngineV2, RaggedInferenceEngineConfig)
    from deepspeed_tpu.models.transformer import CausalLM, TransformerConfig

    if _model is None:
        _model = CausalLM(TransformerConfig(
            vocab_size=VOCAB, hidden_size=64, intermediate_size=128,
            num_layers=2, num_heads=2, max_seq_len=256, norm="rmsnorm",
            activation="silu", position="rope"))
    vcfg = RaggedInferenceEngineConfig(
        max_ragged_batch_size=128, max_ragged_sequence_count=max_seqs,
        max_chunk_tokens=32, kv_blocks=kv_blocks, kv_block_size=8,
        max_tracked_sequences=32)
    eng = InferenceEngineV2(_model, params=_params, config=vcfg)
    _params = eng.params
    return eng


class TestChaosUnderLockDebug:
    def test_supervisor_kill_and_autoscaler_churn_clean(self):
        """ISSUE 14 satellite: one fault-injection chaos run (replica
        crash -> supervisor restart, plus autoscaler-path membership
        churn: grow + evacuating shrink) under RankedLock debug mode —
        no rank-order violations, no over-threshold holds. The hold
        threshold is generous (30s) so only a genuine wedge-while-locked
        could trip it on a loaded CI machine."""
        from deepspeed_tpu.serving import ServingConfig, ServingFrontend

        state = enable_lock_debug(hold_threshold_s=30.0,
                                  raise_on_violation=True)
        try:
            cfg = ServingConfig(
                max_queue_depth=64, default_max_new_tokens=4,
                fault_tolerance={"enabled": True,
                                 "restart_backoff_s": 0.05,
                                 "restart_backoff_max_s": 0.2,
                                 "supervisor_poll_s": 0.02,
                                 "max_retries": 3},
                faults={"enabled": True,
                        "schedule": [{"kind": "crash", "replica": 0,
                                      "at_step": 2}]})
            fe = ServingFrontend.from_engine_factory(
                _tiny_engine, cfg.model_copy(
                    update={"num_replicas": 2}))
            try:
                rng = np.random.default_rng(0)
                handles = [fe.submit(
                    rng.integers(0, VOCAB, size=int(n)).tolist(),
                    max_new_tokens=4)
                    for n in rng.integers(8, 20, size=10)]
                assert fe.wait_all(handles, timeout=180)
                # the injected crash actually fired and was survived
                assert fe.injector.fired_events()
                # membership churn: grow, then evacuating shrink
                rid = fe.add_replica()
                more = [fe.submit(
                    rng.integers(0, VOCAB, size=12).tolist(),
                    max_new_tokens=4) for _ in range(4)]
                assert fe.wait_all(more, timeout=120)
                fe.remove_replica(rid, timeout_s=30.0)
            finally:
                fe.shutdown(drain=False, timeout=10)
            assert state.violations == [], state.violations
            assert state.over_holds == [], state.over_holds
        finally:
            disable_lock_debug()
