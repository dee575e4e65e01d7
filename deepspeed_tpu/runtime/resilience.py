"""Preemption-safe self-healing training: supervisor, watchdog, rollback.

PR 5 made the *serving* stack survive replica death; this module gives the
*training* loop the same property (docs/TRAINING.md "Fault tolerance").
Counterpart of the reference's elasticity/checkpoint-engine capabilities
(``deepspeed/elasticity/``, Nebula checkpoint engine) recast for preemptible
TPU fleets, reusing the supervisor/backoff/chaos idioms proven out in
``serving/supervisor.py`` and ``serving/faults.py``:

- :class:`TrainingSupervisor` wraps the train loop. SIGTERM (the cloud
  preemption notice) triggers an *urgent* bounded-time checkpoint save
  inside the grace window; a crash, a wedged step, or an anomaly storm
  triggers restart-from-``latest`` with exponential backoff + seeded
  jitter and a circuit breaker (mirroring the serving supervisor). Resume
  is *deterministic*: params/moments (exact fp32), LR schedule,
  :class:`~.engine.ScaleState`, the RNG stream (``micro_steps`` replays
  the ``fold_in`` fold points), and the data-iterator position
  (``DeepSpeedTpuDataLoader.state_dict``) are all restored, so an
  interrupted+resumed run reproduces the uninterrupted loss curve
  byte-for-byte (asserted in tests/test_train_resilience.py).
- :class:`StepWatchdog`: a host-side thread with a rolling-median
  step-time baseline. A wedged step (stuck device call) is detected, the
  flight recorder is dumped, and the supervisor restarts from ``latest``
  on a fresh engine instead of hanging forever.
- Anomaly guards extend the engine's overflow/skip-step machinery (the
  jitted update already skips any non-finite-gradient step in *every*
  precision, not just fp16): the supervisor counts consecutive
  NaN/inf-gradient or loss-spike steps and, after K in a row, rolls back
  to the last good checkpoint instead of burning the run.
- :class:`TrainFaultInjector`: seeded, scripted training faults
  (``crash``/``sigterm``/``nan_grads``/``slow_step`` at exact step
  indices) in the style of ``serving/faults.py``, driving the chaos
  suite. Disabled = zero hooks anywhere.

Everything defaults off: with no ``resilience:`` block (and no supervisor
constructed) training behavior is byte-for-byte historical.
"""

from __future__ import annotations

import dataclasses
import math
import os
import random
import signal
import statistics
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from pydantic import Field

from ..utils.locks import RankedLock
from ..utils.logging import logger
from ..utils.restart import RestartPolicy
from .config_utils import DSConfigModel

# --------------------------------------------------------------------- config

TRAIN_FAULT_KINDS = ("crash", "sigterm", "nan_grads", "slow_step")


class TrainFaultsConfig(DSConfigModel):
    """``resilience.faults: {...}`` TEST-ONLY deterministic training fault
    injection (docs/CONFIG.md): a seeded schedule of crashes, preemption
    signals, NaN gradient storms, and wedged-step latency, driving the
    chaos suite (tests/test_train_resilience.py). Disabled = no hooks —
    byte-for-byte the uninstrumented training loop."""

    enabled: bool = False
    seed: int = 0
    # entries: {"kind": "crash"|"sigterm"|"nan_grads"|"slow_step",
    #           "at_step": k | "at_step_range": [lo, hi] (seeded draw),
    #           "duration_s": t (slow_step wedge length),
    #           "count": c (firings allowed; 0 = every time)}
    schedule: List[Dict[str, Any]] = Field(default_factory=list)

    def build_injector(self) -> Optional["TrainFaultInjector"]:
        if not self.enabled:
            return None
        return TrainFaultInjector(self.schedule, seed=self.seed)


class ResilienceConfig(DSConfigModel):
    """``resilience: {...}`` block on ``DeepSpeedTpuConfig``
    (docs/CONFIG.md, docs/TRAINING.md "Fault tolerance"). Consumed by
    :class:`TrainingSupervisor`; the block existing changes nothing by
    itself — constructing the supervisor is the opt-in, and with
    ``enabled: false`` the supervisor refuses to run."""

    enabled: bool = False
    # checkpoint root; 'latest' inside it is the auto-resume anchor
    save_dir: Optional[str] = None
    # periodic checkpoint cadence in optimizer steps (0 = only urgent /
    # caller-driven saves); saves are skipped while an anomaly streak is
    # open so 'latest' always names a last-GOOD state
    save_interval_steps: int = 0
    # preemption: install a SIGTERM handler (main thread only) and
    # complete an urgent synchronous save within this grace window
    handle_sigterm: bool = True
    preempt_grace_s: float = 30.0
    # restart backoff + circuit breaker (serving supervisor idiom):
    # base * 2^(failures_in_window - 1), capped, with seeded jitter;
    # max_restarts_in_window failures inside restart_window_s parks the
    # run (status "parked") instead of looping forever
    restart_backoff_s: float = 0.5
    restart_backoff_max_s: float = 30.0
    restart_backoff_jitter: float = 0.2
    seed: int = 0
    max_restarts_in_window: int = 3
    restart_window_s: float = 3600.0
    # step watchdog: a step outrunning max(step_timeout_s,
    # watchdog_factor x rolling-median) is declared wedged. With
    # step_timeout_s == 0 the auto baseline arms only after
    # watchdog_min_steps completed steps (XLA compiles make the first
    # steps wild). Wedge recovery needs an engine_factory — the stuck
    # thread owns the old engine.
    watchdog_enabled: bool = True
    step_timeout_s: float = 0.0
    watchdog_factor: float = 10.0
    watchdog_min_steps: int = 5
    watchdog_poll_s: float = 0.5
    # anomaly guards: a step is anomalous when the update skipped on a
    # non-finite gradient norm (the engine's overflow gate — all
    # precisions), the loss is non-finite, or the loss exceeds
    # loss_spike_factor x the rolling median of the last loss_window
    # good losses (0 disables the spike check). K consecutive anomalies
    # roll the run back to the last good checkpoint.
    anomaly_detection: bool = True
    loss_spike_factor: float = 10.0
    loss_window: int = 20
    max_consecutive_anomalies: int = 3
    # test-only deterministic fault injection
    faults: TrainFaultsConfig = Field(default_factory=TrainFaultsConfig)


# ------------------------------------------------------------ fault injection


class InjectedTrainFault(RuntimeError):
    """The scripted training failure. A plain RuntimeError subclass on
    purpose: the supervisor must treat it exactly like a real crash."""


@dataclasses.dataclass
class TrainFaultEvent:
    kind: str                       # one of TRAIN_FAULT_KINDS
    at_step: Optional[int] = None   # optimizer-step index
    duration_s: float = 0.0         # slow_step wedge length
    count: int = 1                  # firings allowed; 0 = every time
    error: str = "injected train fault"
    fired: int = 0

    def _matches(self, step: int) -> bool:
        if self.at_step is None:
            return False
        if self.count != 0 and self.fired >= self.count:
            return False
        return step >= self.at_step


class TrainFaultInjector:
    """Seeded, scripted schedule of :class:`TrainFaultEvent`.

    ``on_step(step)`` is consulted once per optimizer step *before* the
    step runs: ``crash`` raises :class:`InjectedTrainFault` into the
    loop's normal crash path, ``slow_step`` sleeps (the stuck-device-call
    shape the watchdog detects), and ``sigterm``/``nan_grads`` events are
    returned to the caller (the supervisor delivers the signal / poisons
    the gradient accumulator). ``at_step_range: [lo, hi]`` draws the step
    from the seeded RNG at construction — same seed, same failure story."""

    # ``events`` is immutable after construction; the firing ledger is
    # multi-writer (docs/CONCURRENCY.md)
    _GUARDED_BY = {"fired_log": "_lock"}

    def __init__(self, schedule: List[Dict[str, Any]], seed: int = 0):
        self.seed = int(seed)
        self.rng = random.Random(self.seed)
        self.events: List[TrainFaultEvent] = []
        for raw in schedule:
            e = dict(raw)
            rng_range = e.pop("at_step_range", None)
            ev = TrainFaultEvent(**e)
            if rng_range is not None:
                ev.at_step = self.rng.randint(int(rng_range[0]),
                                              int(rng_range[1]))
            if ev.kind not in TRAIN_FAULT_KINDS:
                raise ValueError(f"unknown train fault kind {ev.kind!r} "
                                 f"(expected one of {TRAIN_FAULT_KINDS})")
            if ev.at_step is None:
                raise ValueError(f"{ev.kind} fault needs at_step "
                                 "(or at_step_range)")
            self.events.append(ev)
        self._lock = RankedLock("train.faults")
        self.fired_log: List[tuple] = []   # (kind, step, monotonic t)

    def _take(self, step: int) -> List[TrainFaultEvent]:
        with self._lock:
            hits = [ev for ev in self.events if ev._matches(step)]
            for ev in hits:
                ev.fired += 1
                self.fired_log.append((ev.kind, step, time.monotonic()))
        return hits

    def fired_events(self) -> List[tuple]:
        with self._lock:
            return list(self.fired_log)

    def on_step(self, step: int,
                handler: Optional[Callable[[TrainFaultEvent], None]] = None
                ) -> List[TrainFaultEvent]:
        """Pre-step hook. Sleeps wedges itself; ``sigterm``/``nan_grads``
        events go through ``handler`` (or the return list when none is
        given); a ``crash`` raises LAST, after every co-scheduled event
        was delivered — all taken events count as fired, so none may be
        silently swallowed by the raise."""
        out = []
        crash: Optional[TrainFaultEvent] = None
        for ev in self._take(step):
            if ev.kind == "slow_step":
                time.sleep(ev.duration_s)
            elif ev.kind == "crash":
                crash = ev
            elif handler is not None:
                handler(ev)
            else:
                out.append(ev)
        if crash is not None:
            raise InjectedTrainFault(
                f"{crash.error} (crash at step {step})")
        return out


# ----------------------------------------------------------------- watchdog


class StepWatchdog:
    """Host-side wedged-step detector.

    The stepping thread brackets each optimizer step with
    :meth:`step_begin`/:meth:`step_end`; this thread polls and declares a
    wedge when the in-flight step outruns ``max(step_timeout_s, factor x
    rolling-median step time)``. With ``step_timeout_s == 0`` the
    auto-baseline arms only after ``min_samples`` completed steps — the
    first steps include XLA compiles and would poison the median. The
    watchdog only *detects* (sets :attr:`wedged`, fires ``on_wedge``
    once); recovery is the supervisor's job — the wedged thread is stuck
    inside a device call nobody can interrupt."""

    # the duration ring is the only cross-thread structure; the step
    # bracket (``_step_started``) is a single-writer watermark
    _GUARDED_BY = {"_durations": "_dur_lock"}

    def __init__(self, poll_s: float = 0.5, step_timeout_s: float = 0.0,
                 factor: float = 10.0, min_samples: int = 5,
                 on_wedge: Optional[Callable[[float], None]] = None,
                 history: int = 64):
        self.poll_s = float(poll_s)
        self.step_timeout_s = float(step_timeout_s)
        self.factor = float(factor)
        self.min_samples = int(min_samples)
        self.on_wedge = on_wedge
        self._durations: "deque[float]" = deque(maxlen=history)
        # guards _durations: the stepping thread appends while this
        # thread medians — an unguarded sort over a mutating deque
        # raises and would silently kill the watchdog (the one thread
        # that must not die quietly)
        self._dur_lock = RankedLock("train.watchdog.durations")
        self._step_started: Optional[float] = None
        self.wedged = threading.Event()
        self._stop = threading.Event()
        self.thread = threading.Thread(target=self._loop, daemon=True,
                                       name="train-step-watchdog")

    # hooks for the stepping thread ------------------------------------
    def step_begin(self) -> None:
        self._step_started = time.monotonic()

    def step_end(self, duration_s: float) -> None:
        self._step_started = None
        with self._dur_lock:
            self._durations.append(float(duration_s))

    def step_abort(self) -> None:
        """Close the bracket without recording (a step cut short by a
        preemption notice is not a latency sample)."""
        self._step_started = None

    # ------------------------------------------------------------------
    def timeout_s(self) -> Optional[float]:
        """Current wedge threshold: ``max(step_timeout_s, factor x
        rolling median)`` — the documented contract. The fixed floor
        alone applies before the median arms (so a configured timeout
        starts protecting from step one); with no floor the watchdog is
        unarmed (None) until ``min_samples`` steps completed."""
        with self._dur_lock:
            samples = list(self._durations)
        auto = (self.factor * statistics.median(samples)
                if len(samples) >= max(1, self.min_samples) else None)
        if self.step_timeout_s > 0:
            return self.step_timeout_s if auto is None \
                else max(self.step_timeout_s, auto)
        return auto

    def start(self) -> None:
        self.thread.start()

    def stop(self, timeout: float = 2.0) -> None:
        self._stop.set()
        if self.thread.is_alive():
            self.thread.join(timeout)

    def _loop(self) -> None:
        while not self._stop.is_set():
            try:
                started = self._step_started
                limit = self.timeout_s()
            except Exception:  # pragma: no cover — the watchdog must
                self._stop.wait(self.poll_s)  # never die of its own bug
                continue
            if started is not None and limit is not None:
                stuck_for = time.monotonic() - started
                if stuck_for > limit:
                    self.wedged.set()
                    logger.error(
                        f"train watchdog: step wedged for "
                        f"{stuck_for:.2f}s (limit {limit:.2f}s)")
                    if self.on_wedge is not None:
                        try:
                            self.on_wedge(stuck_for)
                        except Exception:  # pragma: no cover - defensive
                            pass
                    return          # one detection per watchdog instance
            self._stop.wait(self.poll_s)


# --------------------------------------------------------------- supervisor


class TrainingSupervisor:
    """Self-healing wrapper around ``engine.train_batch()``.

    ``run(num_steps)`` drives the engine to ``num_steps`` optimizer
    steps, auto-resuming from the ``latest`` checkpoint in ``save_dir``
    first (so calling ``run`` again after a preemption or in a restarted
    process IS the resume path). The step loop runs on a worker thread so
    the supervisor can abandon a wedged step; crashes, wedges, and
    anomaly storms restart from ``latest`` with backoff + a circuit
    breaker. Returns a status dict (``status`` in ``completed`` /
    ``preempted`` / ``parked`` plus the stats counters).

    Engine contract: the engine has ``training_dataloader`` attached
    (``deepspeed_tpu.initialize(..., training_data=...)``) so
    ``train_batch()`` owns the batch stream, and ``engine_factory`` (when
    given) rebuilds an equivalent engine — required for wedge recovery
    (the stuck thread owns the old engine) and for restarts before any
    checkpoint exists."""

    def __init__(self, engine=None, engine_factory: Optional[Callable] = None,
                 config: Optional[ResilienceConfig] = None,
                 save_dir: Optional[str] = None, journal=None):
        if engine is None and engine_factory is None:
            raise ValueError("TrainingSupervisor needs an engine or an "
                             "engine_factory")
        self.engine_factory = engine_factory
        self._engine = engine if engine is not None else engine_factory()
        if config is None:
            config = self._engine.config.resilience
        elif isinstance(config, dict):
            config = ResilienceConfig(**config)
        self.config = config
        self.save_dir = save_dir or self.config.save_dir
        if not self.save_dir:
            raise ValueError("resilience needs a save_dir (config "
                             "resilience.save_dir or the save_dir argument)")
        self.injector = self.config.faults.build_injector()
        # ops journal (docs/OBSERVABILITY.md "The ops event journal"):
        # restarts, parks, preemption saves, anomaly rollbacks,
        # checkpoint publications and wedges land in the SAME
        # schema-validated stream the serving stack uses, so a
        # train+serve host has one merged incident timeline
        if journal is None:
            from ..telemetry.journal import OpsJournal

            journal = OpsJournal(capacity=512, source="training")
        self.journal = journal
        self.rng = random.Random(self.config.seed)
        self.stats: Dict[str, Any] = {
            "train_restarts": 0, "steps_lost": 0, "anomaly_rollbacks": 0,
            "preemptions": 0, "wedges": 0, "urgent_save_s": None,
            "parked": False}
        # (global_step, loss) per completed step, restarts appending the
        # replayed steps again — losses_by_step() keeps the last write
        self.loss_log: List[tuple] = []
        self.restart_log: List[dict] = []
        self.dump_paths: List[dict] = []
        self._gen = 0                       # attempt generation token
        self._preempt = threading.Event()
        # the serving supervisor's backoff/breaker discipline, shared
        # implementation (utils/restart.py)
        self._restart_policy = RestartPolicy(
            self.config.restart_backoff_s, self.config.restart_backoff_max_s,
            self.config.restart_backoff_jitter,
            self.config.max_restarts_in_window, self.config.restart_window_s,
            self.rng)
        # consecutive-anomaly count of the live attempt, mirrored out of
        # the worker so the preemption path can refuse to publish an
        # anomalous state as 'latest'
        self._anomaly_streak = 0
        self._signal_installed = False
        self._prev_handler = None
        self._recorder = None

    # ------------------------------------------------------------ properties
    @property
    def engine(self):
        return self._engine

    def losses_by_step(self) -> Dict[int, float]:
        """Per-step losses with replayed steps collapsed (last write
        wins) — the resume-parity comparison surface."""
        return {step: loss for step, loss in self.loss_log}

    # ------------------------------------------------------------------ run
    def run(self, num_steps: int) -> Dict[str, Any]:
        cfg = self.config
        if not cfg.enabled:
            raise ValueError("resilience.enabled is false; enable it (or "
                             "drive engine.train_batch yourself) — a "
                             "disabled supervisor supervising would be a lie")
        self._install_sigterm()
        # a preemption honored by a PREVIOUS run() (urgent save done) must
        # not poison this one — calling run() again IS the resume path
        self._preempt.clear()
        try:
            self._restore_latest()
            while True:
                if self.stats["parked"]:
                    return self._status("parked")
                if self._preempt.is_set():
                    # preempted outside a clean boundary exit (e.g. during
                    # restart backoff): 'latest' already holds the last
                    # checkpoint — do not save mid-flight state
                    return self._status("preempted")
                box = self._attempt(num_steps)
                outcome = box["outcome"]
                if outcome == "completed":
                    return self._status("completed")
                if outcome == "preempted":
                    if self._anomaly_streak > 0:
                        # 'latest' must keep naming the last GOOD state:
                        # an urgent save here would publish the anomalous
                        # params and make a later rollback restore them
                        self.stats["preemptions"] += 1
                        logger.warning(
                            f"preempted with {self._anomaly_streak} "
                            "consecutive anomalies open: skipping the "
                            "urgent save — resume falls back to the last "
                            "good checkpoint")
                    else:
                        self._urgent_save()
                    return self._status("preempted")
                # crash / wedge / anomaly → supervised restart (the
                # anomaly_rollbacks counter is bumped inside
                # _handle_failure only when a rollback actually happened)
                if not self._handle_failure(outcome, box):
                    return self._status("parked")
        finally:
            self._restore_sigterm()

    # -------------------------------------------------------------- attempt
    def _attempt(self, num_steps: int) -> Dict[str, Any]:
        cfg = self.config
        gen = self._gen
        engine = self._engine
        box: Dict[str, Any] = {"outcome": None, "error": None,
                               "step_at_exit": None}
        watchdog = None
        if cfg.watchdog_enabled:
            watchdog = StepWatchdog(
                poll_s=cfg.watchdog_poll_s,
                step_timeout_s=cfg.step_timeout_s,
                factor=cfg.watchdog_factor,
                min_samples=cfg.watchdog_min_steps)
            watchdog.start()

        def loop():
            consecutive = 0
            self._anomaly_streak = 0        # fresh attempt, fresh streak
            good_losses: "deque[float]" = deque(maxlen=max(1, cfg.loss_window))
            try:
                while engine.global_steps < num_steps:
                    if self._gen != gen:
                        box["outcome"] = "superseded"
                        return
                    if self._preempt.is_set():
                        box["outcome"] = "preempted"
                        box["step_at_exit"] = engine.global_steps
                        return
                    step = engine.global_steps
                    # the injector hook runs INSIDE the watchdog bracket:
                    # slow_step models a wedged device call, and a wedge
                    # outside the bracket would be invisible. A step that
                    # changes the curriculum difficulty recompiles —
                    # minutes vs a sub-second median — so it is exempt
                    # from the bracket entirely (neither wedge-checked
                    # nor median-recorded): missing a real wedge on a
                    # compile step beats parking a healthy run mid-compile
                    bracket = watchdog is not None \
                        and not self._expect_recompile(engine, step)
                    if bracket:
                        watchdog.step_begin()
                    t0 = time.monotonic()
                    if self.injector is not None:
                        # may raise (crash, delivered last) or sleep
                        # (slow_step) here; sigterm/nan_grads arrive via
                        # the handler even when a crash is co-scheduled
                        def handle(ev):
                            if ev.kind == "sigterm":
                                self._deliver_sigterm()
                            elif ev.kind == "nan_grads":
                                self._poison_grads(engine)

                        self.injector.on_step(step, handler=handle)
                        if self._preempt.is_set():
                            if bracket:
                                watchdog.step_abort()
                            continue        # exit at loop top, pre-step
                    loss = float(engine.train_batch())
                    dt = time.monotonic() - t0
                    if bracket:
                        watchdog.step_end(dt)
                    if self._gen != gen:
                        box["outcome"] = "superseded"
                        return
                    self.loss_log.append((engine.global_steps, loss))
                    anomaly = self._is_anomaly(engine, loss, good_losses)
                    if anomaly:
                        consecutive += 1
                        self._anomaly_streak = consecutive
                        if consecutive >= max(1, cfg.max_consecutive_anomalies):
                            box["outcome"] = "anomaly"
                            box["step_at_exit"] = engine.global_steps
                            return
                    else:
                        consecutive = 0
                        self._anomaly_streak = 0
                        good_losses.append(loss)
                        if cfg.save_interval_steps > 0 and \
                                engine.global_steps % cfg.save_interval_steps == 0:
                            self._save(engine)
                box["outcome"] = "completed"
                box["step_at_exit"] = engine.global_steps
            except BaseException as e:  # noqa: BLE001 — becomes the crash path
                box["outcome"] = "crash"
                box["error"] = e
                box["step_at_exit"] = engine.global_steps

        worker = threading.Thread(target=loop, daemon=True,
                                  name="train-supervised-loop")
        worker.start()
        try:
            while worker.is_alive():
                worker.join(0.05)
                if watchdog is not None and watchdog.wedged.is_set() \
                        and worker.is_alive():
                    # abandon the stuck worker: it owns the engine until
                    # its device call returns, so bump the generation (it
                    # exits at the next loop-top check) and recover on a
                    # FRESH engine. Return a fresh dict — the abandoned
                    # worker still holds `box` and may scribble on it.
                    self._gen += 1
                    self.stats["wedges"] += 1
                    self.journal.emit("train_wedge",
                                      step=int(engine.global_steps))
                    self._dump_flight_recorder(engine, "train_wedge")
                    return {"outcome": "wedge", "error": None,
                            "step_at_exit": engine.global_steps}
        finally:
            if watchdog is not None:
                watchdog.stop()
        return box

    # ------------------------------------------------------------- anomalies
    @staticmethod
    def _expect_recompile(engine, step: int) -> bool:
        """True when the upcoming step changes the curriculum difficulty:
        the batch shape changes, so train_batch pays an XLA compile that
        can outrun the rolling-median wedge threshold by orders of
        magnitude. Pure probe — get_difficulty does not mutate the
        scheduler (``_apply_curriculum`` inside the step does the actual
        update, with the same ``step + 1`` the engine uses)."""
        sched = getattr(engine, "curriculum_scheduler", None)
        if sched is None:
            return False
        try:
            return sched.get_difficulty(step + 1) != \
                sched.get_difficulty(step)
        except Exception:       # a broken schedule fails in train_batch,
            return False        # with its real error — not in this probe

    def _is_anomaly(self, engine, loss: float,
                    good_losses: "deque[float]") -> bool:
        cfg = self.config
        if not cfg.anomaly_detection:
            return False
        if not math.isfinite(loss):
            return True
        m = getattr(engine, "_last_metrics", None)
        if m is not None and bool(np.asarray(m.get("overflow", False))):
            # the jitted update skipped this step on a non-finite grad
            # norm (every precision — the fp16 scale automaton additionally
            # rescales); one skip is the bounded step-skip, K in a row is
            # the rollback trigger
            return True
        if cfg.loss_spike_factor > 0 and len(good_losses) >= 3:
            med = statistics.median(good_losses)
            if med > 0 and loss > cfg.loss_spike_factor * med:
                return True
        return False

    @staticmethod
    def _poison_grads(engine) -> None:
        """nan_grads injection: poison the gradient accumulator so this
        step's update sees a non-finite norm (eager elementwise op —
        preserves each leaf's sharding, no resharding on the next jit)."""
        import jax

        nan = float("nan")
        engine.state = engine.state._replace(
            grad_acc=jax.tree.map(lambda g: g * nan, engine.state.grad_acc))

    # ----------------------------------------------------------- checkpoints
    def _client_state(self, engine) -> Dict[str, Any]:
        cs: Dict[str, Any] = {"resilience": {"format": 1}}
        loader = getattr(engine, "training_dataloader", None)
        if loader is not None and hasattr(loader, "state_dict"):
            try:
                cs["dataloader"] = loader.state_dict()
            except NotImplementedError:
                pass        # sampler/iterable sources own their position
        return cs

    def _save(self, engine, urgent: bool = False) -> None:
        engine.save_checkpoint(self.save_dir,
                               client_state=self._client_state(engine),
                               urgent=urgent)
        # journaled AFTER the save returns: the event records a
        # checkpoint that actually published (atomic 'latest' swap)
        self.journal.emit("checkpoint_saved",
                          step=int(engine.global_steps), urgent=urgent)

    def _restore_latest(self) -> bool:
        """Load ``latest`` (if any) into the current engine and restore
        the data-iterator position; returns True when a checkpoint was
        loaded. The gradient accumulator is explicitly zeroed — a crash
        mid-accumulation leaves stale partial sums the checkpoint knows
        nothing about."""
        import jax
        import jax.numpy as jnp

        engine = self._engine
        path, cs = engine.load_checkpoint(self.save_dir)
        if path is None:
            return False
        engine.state = engine.state._replace(
            grad_acc=jax.tree.map(jnp.zeros_like, engine.state.grad_acc))
        loader = getattr(engine, "training_dataloader", None)
        dl_state = (cs or {}).get("dataloader")
        if loader is not None and dl_state is not None \
                and hasattr(loader, "load_state_dict"):
            loader.load_state_dict(dl_state)
            engine.reset_data_iterator()
        elif loader is not None:
            # the checkpoint carries no data position (sampler/iterable
            # source — state_dict raised at save time): params rolled
            # back but the batch stream cannot, so replayed steps may see
            # different batches. Never silent — this voids the
            # byte-for-byte resume contract (docs/TRAINING.md).
            logger.warning(
                "resume: checkpoint has no dataloader position (source "
                "is not resumable) — replayed steps may train on "
                "different batches; resume is NOT byte-for-byte for "
                "this data source")
        return True

    def _urgent_save(self) -> None:
        """The SIGTERM grace-window save: joins any in-flight async
        write, completes synchronously, and records the measured wall
        time against the grace budget."""
        cfg = self.config
        engine = self._engine
        span = engine.tracer.begin("train_preempt_save", trace_id="train",
                                   attrs={"global_step": engine.global_steps})
        t0 = time.monotonic()
        try:
            self._save(engine, urgent=True)
        finally:
            span.end()
        dt = getattr(engine, "last_urgent_save_s", None)
        dt = float(dt) if dt is not None else time.monotonic() - t0
        self.stats["urgent_save_s"] = dt
        self.stats["preemptions"] += 1
        self.journal.emit("train_preempt_save",
                          step=int(engine.global_steps),
                          save_s=round(dt, 4),
                          within_grace=dt <= cfg.preempt_grace_s)
        if dt > cfg.preempt_grace_s:
            logger.error(f"urgent checkpoint took {dt:.2f}s — exceeds the "
                         f"{cfg.preempt_grace_s:.0f}s preemption grace "
                         "window; shrink the model state per host or raise "
                         "the grace budget")
        else:
            logger.info(f"urgent checkpoint saved in {dt:.2f}s "
                        f"(grace {cfg.preempt_grace_s:.0f}s)")

    # --------------------------------------------------------------- failure
    def _handle_failure(self, reason: str, box: Dict[str, Any]) -> bool:
        """Backoff (seeded jitter), circuit-breaker check, engine
        replacement, restore-from-latest. Returns False when the run
        parks (breaker tripped or recovery is impossible)."""
        cfg = self.config
        now = time.monotonic()
        err = box.get("error")
        logger.warning(f"train supervisor: {reason} at step "
                       f"{box.get('step_at_exit')}"
                       + (f" ({type(err).__name__}: {err})" if err else ""))
        n, backoff = self._restart_policy.record_failure(now)
        if backoff is None:             # circuit breaker tripped
            self.stats["parked"] = True
            self.journal.emit("train_parked", failures=n,
                              reason="circuit_breaker")
            logger.error(f"train supervisor PARKED after {n} failures in "
                         f"{cfg.restart_window_s:.0f}s window — not "
                         "restarting a run that keeps dying")
            return False
        needs_fresh_engine = reason == "wedge"
        has_checkpoint = os.path.exists(os.path.join(self.save_dir, "latest"))
        if (needs_fresh_engine or not has_checkpoint) \
                and self.engine_factory is None:
            # a wedged thread owns the old engine; and with no checkpoint
            # a restart must rebuild virgin state — both need the factory
            self.stats["parked"] = True
            self.journal.emit("train_parked", failures=n,
                              reason="no_engine_factory")
            logger.error(
                "train supervisor PARKED: recovery needs an engine_factory "
                f"({'wedged step' if needs_fresh_engine else 'no checkpoint yet'})")
            return False
        logger.warning(f"train supervisor: restart {n} in {backoff:.2f}s")
        if self._preempt.wait(backoff):
            return True                 # run() surfaces the preemption
        t0 = time.monotonic()
        if needs_fresh_engine or (not has_checkpoint
                                  and self.engine_factory is not None):
            self._engine = self.engine_factory()
        restored = self._restore_latest()
        step_at_exit = int(box.get("step_at_exit") or 0)
        steps_lost = max(0, step_at_exit - self._engine.global_steps)
        self.stats["train_restarts"] += 1
        self.stats["steps_lost"] += steps_lost
        if reason == "anomaly":
            # counted HERE, after the restore: a parked anomaly storm
            # never rolled anything back and must not report one
            self.stats["anomaly_rollbacks"] += 1
            self.journal.emit("train_anomaly_rollback",
                              step=step_at_exit,
                              resumed_step=int(self._engine.global_steps))
        recovery_s = time.monotonic() - t0
        self.journal.emit("train_restart", reason=reason, attempt=n,
                          steps_lost=steps_lost,
                          resumed_step=int(self._engine.global_steps),
                          recovery_s=round(recovery_s, 4))
        self.restart_log.append({
            "reason": reason, "attempt": n,
            "from_step": step_at_exit,
            "resumed_step": int(self._engine.global_steps),
            "steps_lost": steps_lost, "restored": restored,
            "backoff_s": backoff, "recovery_s": recovery_s})
        self._engine.tracer.begin(
            "train_restart", trace_id="train",
            attrs={"reason": reason, "attempt": n,
                   "steps_lost": steps_lost,
                   "resumed_step": int(self._engine.global_steps)}).end()
        if reason != "wedge" and self._engine.tracer.enabled:
            # wedges already dumped pre-restart; crash/anomaly restarts
            # dump only under telemetry, like serving restarts
            self._dump_flight_recorder(self._engine, f"train_{reason}")
        self._publish_gauges()
        logger.warning(
            f"train supervisor: restarted from step "
            f"{self._engine.global_steps} ({reason}; {steps_lost} steps "
            f"lost; {recovery_s:.2f}s)")
        return True

    # ------------------------------------------------------------- telemetry
    def _dump_flight_recorder(self, engine, reason: str) -> None:
        """Post-incident record (serving restart-dump idiom): spans in
        flight at the wedge/crash + whatever metrics providers were
        registered. Never raises — best effort by construction."""
        try:
            from ..telemetry import FlightRecorder

            if self._recorder is None or self._recorder.tracer is not engine.tracer:
                self._recorder = FlightRecorder(engine.tracer)
            self._recorder.snapshot_metrics()
            self.dump_paths.append(self._recorder.dump(reason=reason))
        except Exception as e:  # pragma: no cover - defensive
            logger.warning(f"train flight-recorder dump failed: {e!r}")

    def _publish_gauges(self) -> None:
        """docs/OBSERVABILITY.md gauge names: Train/train_restarts,
        Train/steps_lost, Train/anomaly_rollbacks through the monitor
        fan-out (same path as the loss curves)."""
        mon = getattr(self._engine, "monitor", None)
        if mon is None:
            return
        step = int(self._engine.global_steps)
        try:
            mon.write_events([
                ("Train/train_restarts", self.stats["train_restarts"], step),
                ("Train/steps_lost", self.stats["steps_lost"], step),
                ("Train/anomaly_rollbacks",
                 self.stats["anomaly_rollbacks"], step)])
        except Exception:  # pragma: no cover - defensive
            pass

    # --------------------------------------------------------------- signals
    def _install_sigterm(self) -> None:
        if not self.config.handle_sigterm:
            return
        if threading.current_thread() is not threading.main_thread():
            return
        try:
            self._prev_handler = signal.signal(signal.SIGTERM,
                                               self._on_sigterm)
            self._signal_installed = True
        except (ValueError, OSError):   # non-main interpreter contexts
            self._signal_installed = False

    def _restore_sigterm(self) -> None:
        if self._signal_installed:
            try:
                signal.signal(signal.SIGTERM, self._prev_handler
                              if self._prev_handler is not None
                              else signal.SIG_DFL)
            except (ValueError, OSError):  # pragma: no cover - defensive
                pass
            self._signal_installed = False

    def _on_sigterm(self, signum, frame) -> None:
        logger.warning("SIGTERM received: finishing the in-flight step, "
                       "then urgent-checkpointing inside the grace window")
        self._preempt.set()

    def _deliver_sigterm(self) -> None:
        """Injected preemption: go through the real signal machinery when
        our handler is installed (exercises the production path), else
        set the preempt flag directly. Waits for the flag so the worker
        deterministically exits before running another step."""
        if self._signal_installed:
            signal.raise_signal(signal.SIGTERM)
        else:
            self._preempt.set()
        self._preempt.wait(5.0)

    # --------------------------------------------------------- health report
    def health_report(self, recent_events: int = 20) -> Dict[str, Any]:
        """One queryable training-health answer (docs/OBSERVABILITY.md
        "The health report"), the training counterpart of
        ``ServingFrontend.health_report()``: progress, the resilience
        counters, the restart log tail, the open anomaly streak, and the
        recent ops-journal events — merged into a single dict."""
        report = {
            "wall_time": time.time(),
            "global_step": int(self._engine.global_steps),
            "parked": bool(self.stats["parked"]),
            "preempt_pending": self._preempt.is_set(),
            "anomaly_streak": int(self._anomaly_streak),
            "counters": {k: self.stats[k] for k in
                         ("train_restarts", "steps_lost",
                          "anomaly_rollbacks", "preemptions", "wedges")},
            "urgent_save_s": self.stats["urgent_save_s"],
            "restart_log": list(self.restart_log[-5:]),
            "events": self.journal.events(limit=recent_events),
        }
        return report

    def health_report_text(self, recent_events: int = 10) -> str:
        """The training health report rendered for a terminal."""
        r = self.health_report(recent_events=recent_events)
        c = r["counters"]
        lines = [
            "== training health ==",
            f"step={r['global_step']}"
            + ("  PARKED" if r["parked"] else "")
            + ("  PREEMPT-PENDING" if r["preempt_pending"] else "")
            + (f"  anomaly_streak={r['anomaly_streak']}"
               if r["anomaly_streak"] else ""),
            f"restarts={c['train_restarts']} steps_lost={c['steps_lost']} "
            f"rollbacks={c['anomaly_rollbacks']} "
            f"preemptions={c['preemptions']} wedges={c['wedges']}",
        ]
        if r["events"]:
            lines.append("recent events:")
            lines.append(self.journal.render_text(limit=recent_events))
        return "\n".join(lines)

    # ---------------------------------------------------------------- status
    def _status(self, status: str) -> Dict[str, Any]:
        out = {"status": status,
               "completed_steps": int(self._engine.global_steps),
               "restarts": len(self.restart_log),
               "restart_log": list(self.restart_log),
               "dump_paths": list(self.dump_paths)}
        out.update(self.stats)
        return out
