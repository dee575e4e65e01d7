"""``benchmark/dispatch_readers.py`` on hand-built event lists (the shape
``scopes.load`` gives) and on a small recorded chip trace: the pairing of
forward modules with ``ds:dispatch`` annotations by order, the clock offset,
idle time by cause, and the metrics that follow."""

import os

import pytest

from benchmark import dispatch_readers as dr
from benchmark import manifest as mf
from benchmark import readers, scopes, trace

DEV, HOST = "/device:TPU:0", "/host:CPU"
NEW_METRICS = (
    "dev_decode_ms_per_forward", "dev_prefill_us_per_token",
    "decode_time_chunk_share", "decode_time_idle_share",
    "idle_starved_share", "idle_launch_share", "idle_no_work_share",
    "steps_overlapped_share", "steps_starved_share", "prefill_own_share",
    "sat_dev_decode_ms_per_forward", "sat_dev_prefill_us_per_token",
    "sat_decode_time_chunk_share", "sat_idle_starved_share",
    "sat_idle_launch_share")


def module(program, start, dur):
    return {"plane": DEV, "line": "XLA Modules",
            "name": f"jit__forward({program})", "start": start, "dur": dur}


def op(start, dur):
    return {"plane": DEV, "line": "XLA Ops", "name": "%fusion.1 = f32[]",
            "start": start, "dur": dur, "op_name": "jit(_forward)/layers"}


def ann(name, start, dur, **stats):
    return {"plane": HOST, "line": "replica-0", "name": "ds:" + name,
            "start": start, "dur": dur, "stats": stats}


def window(start, dur):
    return {"plane": HOST, "line": "python3", "name": "bench:window",
            "start": start, "dur": dur}


def dispatch(ordinal, start, dur, seqs, chunk, tokens=None, uids=""):
    return ann("dispatch", start, dur, ordinal=ordinal, bucket_seqs=seqs,
               bucket_chunk=chunk, rows=seqs,
               valid_tokens=seqs * chunk if tokens is None else tokens,
               uids=uids)


#: one program a bucket
PROGRAM = {(4, 1): 11, (1, 64): 22, (8, 1): 33}


def simulate(puts, first_ordinal=100, pack=4e-4, pre=3e-4, call=2e-4,
             launch=1e-4, read=1e-4, commit=3e-4, programs=PROGRAM):
    """The scheduler with one step in flight, as events: ``puts`` is a list
    of puts, each a list of forwards ``(seqs, chunk, device seconds)``. A
    module starts when the device is free and ``launch`` after its
    dispatch began; a step reads the put before its own back."""
    events, t, free, ahead, ordinal = [], 0.0, 0.0, None, first_ordinal
    for forwards in puts + [None]:
        step = t
        if forwards is not None:
            events.append(ann("pack", t, pack))
            t += pack
            stage = t
            for seqs, chunk, dur in forwards:
                t += pre
                events.append(dispatch(ordinal, t, call, seqs, chunk))
                start = max(free, t + launch)
                events.append(module(programs[seqs, chunk], start, dur))
                events.append(op(start, dur))
                free, t, ordinal = start + dur, t + call, ordinal + 1
            events.append(ann("stage", stage, t - stage))
        if ahead is not None:
            done = max(t, ahead) + read
            events.append(ann("fetch", t, done - t))
            events.append(ann("commit", done, commit))
            t = done + commit
        flown = forwards is not None and ahead is not None
        events.append(ann("step", step, t - step, overlapped=int(flown),
                          starved=int(flown and ahead < stage + pre + call)))
        ahead = free if forwards is not None else None
    events.append(window(0.0, t))
    return events


DECODE = [(4, 1, 4e-3)]
MIXED = [(4, 1, 4e-3), (1, 64, 9e-3)]
SCHEDULE = [DECODE, DECODE, MIXED, DECODE, [(8, 1, 5e-3)], DECODE, MIXED,
            DECODE, DECODE]


def opened_after(events, dropped_puts):
    """The trace of a profiler that came up after the first puts were
    handed over: their annotations are gone, their modules are not."""
    stages = sorted((e for e in events if e["name"] == "ds:stage"),
                    key=lambda e: e["start"])
    t = stages[dropped_puts]["start"] - 1e-5
    return [e for e in events
            if e["plane"] == DEV or e["name"] == "bench:window"
            or e["start"] >= t]


# ------------------------------------------------------------------ pairing

def test_a_whole_trace_pairs_module_k_with_dispatch_k():
    found = dr.pair(simulate(SCHEDULE))
    assert (found["o0"], found["lead"]) == (100, 0)
    assert found["unpaired_dispatches"] == 0
    for k, (m, d) in enumerate(found["pairs"]):
        assert d["stats"]["ordinal"] == 100 + k
        assert dr.program_of(m) == str(PROGRAM[d["stats"]["bucket_seqs"],
                                               d["stats"]["bucket_chunk"]])
    assert found["programs"] == {(4, 1, 0): "11", (1, 64, 0): "22",
                                 (8, 1, 0): "33"}


@pytest.mark.parametrize("schedule, lead", [
    (SCHEDULE, 1),                                  # one forward ahead
    ([MIXED + [(1, 64, 9e-3)]] + SCHEDULE, 3)])     # a put of three
def test_pairing_finds_the_offset_of_a_trace_that_opens_in_mid_run(
        schedule, lead):
    found = dr.pair(opened_after(simulate(schedule), 1))
    assert (found["o0"], found["lead"]) == (100, lead)
    assert [d for _, d in found["pairs"][:lead]] == [None] * lead
    assert found["pairs"][lead][1]["stats"]["ordinal"] == 100 + lead
    # nearest-annotation pairing would book these under their neighbours
    r = dr.reduce_trace(opened_after(simulate(schedule), 1))
    assert r["forwards"][0]["ordinal"] == 100
    # the bucket of a forward the trace did not see dispatched is its
    # program's
    assert r["forwards"][0]["attrs"] == {"bucket_seqs": 4, "bucket_chunk": 1}


def test_two_buckets_on_one_program_pair_with_nothing():
    shared = dict(PROGRAM)
    shared[8, 1] = shared[4, 1]
    found = dr.pair(simulate(SCHEDULE, programs=shared))
    assert "pairs" not in found and "no offset fits" in found["why"]
    assert "programs under several buckets" in found["why"]
    assert dr.reduce_trace(simulate(SCHEDULE, programs=shared)) == found


def test_a_uniform_trace_that_no_moment_tells_apart_is_not_guessed():
    # one bucket, forwards shorter than the slack two clocks are allowed,
    # the device always a step behind the host: a trace one forward
    # further on reads the same
    short = [(4, 1, 1.2e-3)]
    events = simulate([short] * 12, pack=1e-4, pre=1e-4, commit=1e-4)
    found = dr.pair(events)
    assert "why" in found and "offsets fit" in found["why"]
    # a change of bucket tells them apart
    events = simulate([short] * 6 + [[(8, 1, 1.2e-3)]] + [short] * 5,
                      pack=1e-4, pre=1e-4, commit=1e-4)
    assert dr.pair(events)["lead"] == 0


def test_lost_annotations_and_foreign_traces_say_why():
    events = simulate(SCHEDULE)
    assert "no ds:dispatch" in dr.pair(
        [e for e in events if e["name"] != "ds:dispatch"])["why"]
    holed = [e for e in events if not (
        e["name"] == "ds:dispatch" and e["stats"]["ordinal"] == 103)]
    assert "not consecutive" in dr.pair(holed)["why"]
    assert "no forward module" in dr.pair(
        [e for e in events if e["line"] != "XLA Modules"])["why"]
    assert dr.describe(holed).startswith("cannot pair this trace")


# -------------------------------------------------------------------- clock

@pytest.mark.parametrize("shift", [-1e-3, 0.0, 1e-3])
def test_a_shifted_host_clock_is_recovered_within_its_uncertainty(shift):
    events = simulate(SCHEDULE)
    for e in events:
        if e["plane"] == HOST:
            e["start"] += shift
    r = dr.reduce_trace(events)
    clk = r["clock"]
    # the device is ahead of the annotations by minus the shift
    assert abs(clk["offset_s"] + shift) <= clk["uncertainty_s"]
    # launch (dispatch began -> module start) and read (module end -> fetch
    # end) are the two tightest gaps of the simulation
    assert clk["uncertainty_s"] == pytest.approx((1e-4 + 1e-4) / 2, rel=1e-6)
    assert clk["offset_s"] == pytest.approx(-shift, abs=1e-9)
    # and with the host moved back onto the device's clock, the idle
    # between forwards has the causes it had (the window's edges apart:
    # the mark is the host's)
    unshifted = dr.reduce_trace(simulate(SCHEDULE))
    for cause in ("launch", "in_module"):
        assert r["idle"]["by_cause"][cause] == pytest.approx(
            unshifted["idle"]["by_cause"][cause], abs=1e-9)
    for k in range(3, 9):
        assert r["idle"]["gaps"][k] == {
            c: pytest.approx(v, abs=1e-9)
            for c, v in unshifted["idle"]["gaps"][k].items()}
    assert [f["start"] for f in r["forwards"]] == \
        [f["start"] for f in unshifted["forwards"]]


def test_the_programs_reading_of_starved_and_the_traces_agree():
    # a host that is late for every second step: its pack outlasts the
    # forward ahead
    puts = [DECODE, [(8, 1, 5e-3)], DECODE, DECODE, [(1, 64, 9e-3)],
            DECODE, [(8, 1, 5e-3)], [(8, 1, 5e-3)], DECODE, DECODE]
    events = simulate(puts, pack=4e-4)
    r = dr.reduce_trace(events)
    assert dr.starved_steps(events, r) == {"steps": 10, "program": 0.0,
                                           "trace": 0.0}
    # a host whose pack outlasts every forward but the chunk's
    late = simulate(puts, pack=6e-3)
    r = dr.reduce_trace(late)
    both = dr.starved_steps(late, r)
    # every step finds the device dry but the first (nothing in flight)
    # and the one behind the 9 ms chunk
    assert both == {"steps": 10, "program": 80.0, "trace": 80.0}
    assert "80.0% by the program's is_ready, 80.0% by the trace" in \
        dr.describe(late)


def test_the_monotonic_clock_is_tied_by_the_dispatch_spans():
    events = simulate(SCHEDULE)
    spans = [{"name": "dispatch", "t_start": d["start"] - 5000.0,
              "attrs": {"ordinal": d["stats"]["ordinal"]}}
             for d in dr.dispatches(events)]
    assert dr.monotonic_offset(dr.dispatches(events), spans) == \
        pytest.approx(5000.0)
    assert dr.monotonic_offset(dr.dispatches(events), []) is None


# --------------------------------------------------------------------- idle

def test_the_five_causes_sum_to_the_idle_seconds():
    events = simulate(SCHEDULE)
    # idle inside a running module: a hole in its operations
    first = next(e for e in events if e["line"] == "XLA Ops")
    hole = {"start": first["start"] + 1e-3, "dur": 5e-4}
    events.remove(first)
    events += [op(first["start"], 1e-3),
               op(hole["start"] + hole["dur"],
                  first["dur"] - 1e-3 - hole["dur"])]
    # and the worker waiting for work before the first step
    for e in events:
        e["start"] += 2e-3
    events.append(ann("idle_wait", 0.0, 1.5e-3))
    events.append(window(0.0, max(dr._end(e) for e in events)))
    r = dr.reduce_trace(events)
    idle = r["idle"]
    summed = scopes.summarize(events)
    assert idle["idle_s"] == pytest.approx(summed["idle_s"], rel=1e-9)
    assert sum(idle["by_cause"].values()) == pytest.approx(idle["idle_s"])
    assert idle["by_cause"]["in_module"] == pytest.approx(5e-4)
    assert idle["by_cause"]["no_work"] == pytest.approx(1.5e-3)
    assert idle["by_cause"]["unspanned"] == pytest.approx(5e-4)
    assert all(v > 0 for v in idle["by_cause"].values())
    assert sum(v for _, v in idle["starved_by_phase"]) == \
        pytest.approx(idle["by_cause"]["starved"])
    assert {n for n, _ in idle["starved_by_phase"]} <= {
        "ds:pack", "ds:stage", "ds:dispatch", "ds:fetch", "ds:commit",
        "ds:step"}


def gap_trace(dispatch_at):
    """Two forwards with the device idle over [10, 14) ms between them; the
    second was handed over at ``dispatch_at`` (its call takes 0.2 ms)."""
    return [
        window(0.0, 20e-3),
        ann("stage", 0.5e-3, 1e-3), dispatch(7, 1e-3, 2e-4, 4, 1),
        module(11, 2e-3, 8e-3), op(2e-3, 8e-3),
        ann("stage", dispatch_at - 5e-4, 7e-4),
        dispatch(8, dispatch_at, 2e-4, 1, 64),
        module(22, 14e-3, 5e-3), op(14e-3, 5e-3)]


def in_order(events):
    return list(zip(dr.forward_modules(events), dr.dispatches(events)))


def test_a_gap_whose_forward_was_handed_over_before_it_is_all_launch():
    idle = dr.idle_by_cause(gap_trace(5e-3), in_order(gap_trace(5e-3)))
    assert idle["gaps"] == {1: {"no_work": 0.0, "starved": 0.0,
                                "unspanned": 0.0,
                                "launch": pytest.approx(4e-3),
                                "in_module": 0.0}}


def test_a_gap_whose_forward_was_handed_over_after_it_is_all_the_hosts():
    events = gap_trace(13.8e-3)     # the call ends as the module starts
    idle = dr.idle_by_cause(events, in_order(events))
    (gap,) = idle["gaps"].values()
    assert gap["launch"] == 0.0 and gap["no_work"] == 0.0
    # under ``stage`` from 13.3 ms on, under nothing before
    assert gap["starved"] == pytest.approx(0.7e-3)
    assert gap["unspanned"] == pytest.approx(3.3e-3)
    # (the window's, the first forward's hand-over included: as much again)
    assert dict(idle["starved_by_phase"]) == {
        "ds:stage": pytest.approx(2 * 5e-4),
        "ds:dispatch": pytest.approx(2 * 2e-4)}
    # and split where the call ended, in between
    events = gap_trace(11.8e-3)
    (gap,) = dr.idle_by_cause(events, in_order(events))["gaps"].values()
    assert gap["launch"] == pytest.approx(2e-3)
    assert gap["starved"] + gap["unspanned"] == pytest.approx(2e-3)


def test_innermost_is_found_by_bisection():
    host = [ann("step", 0.0, 10.0), ann("pack", 1.0, 2.0),
            ann("stage", 3.0, 4.0), ann("dispatch", 4.0, 1.0),
            ann("idle_wait", 12.0, 3.0)]
    inner = dr._Innermost(host)
    assert list(inner.pieces(-1.0, 16.0)) == [
        (-1.0, 0.0, None), (0.0, 1.0, "ds:step"), (1.0, 3.0, "ds:pack"),
        (3.0, 4.0, "ds:stage"), (4.0, 5.0, "ds:dispatch"),
        (5.0, 7.0, "ds:stage"), (7.0, 10.0, "ds:step"), (10.0, 12.0, None),
        (12.0, 15.0, "ds:idle_wait"), (15.0, 16.0, None)]
    assert list(inner.pieces(4.2, 4.4)) == [(4.2, 4.4, "ds:dispatch")]


# ------------------------------------------------- a two-request schedule

class Ctx:
    """What a reader is given, with the trace already loaded."""

    def __init__(self, events, spans, window, on_chip=True, monkeypatch=None):
        self.trace = {"window": window} if on_chip else None
        self.result = {"xplane": "unused.pb", "program_spans": spans,
                       "window": window, "chips": 1}
        self.info, self.device = {}, {"platform": "tpu"}
        monkeypatch.setattr(dr.scopes, "load", lambda path: events)


#: ``time.monotonic`` is this far behind the trace's clock
MONO = 1000.0


def two_requests():
    """Request 1 (uid 1) decodes all through; request 2 (uid 2) arrives at
    10 ms, prefills in two chunk forwards, then both decode. Device:
    [1] decode 0-4, 5-9 | [2] chunk 12-22 | [1] decode 23-27 | [2] chunk
    28-38 | [1, 2] decode 40-44, 45-49. Window 0-50 ms."""
    ms = 1e-3
    plan = [(1, 1, 0, 4, "1", 1), (1, 1, 5, 4, "1", 1),
            (1, 64, 12, 10, "2", 64), (1, 1, 23, 4, "1", 1),
            (1, 64, 28, 10, "2", 40), (2, 1, 40, 4, "1 2", 2),
            (2, 1, 45, 4, "1 2", 2)]
    programs = {(1, 1): 5, (1, 64): 6, (2, 1): 7}
    events, spans = [window(0.0, 50 * ms)], []
    for k, (s, c, start, dur, uids, tokens) in enumerate(plan):
        at = (start - 0.5) * ms
        events += [ann("step", at - 0.3 * ms, 0.7 * ms, overlapped=0),
                   ann("stage", at - 0.2 * ms, 0.5 * ms),
                   dispatch(50 + k, at, 0.2 * ms, s, c, tokens, uids),
                   module(programs[s, c], start * ms, dur * ms),
                   op(start * ms, dur * ms),
                   # read back as long after its end as it started after
                   # its dispatch began: the two clocks are one
                   ann("step", (start + dur - 0.3) * ms, 0.8 * ms),
                   ann("fetch", (start + dur - 0.2) * ms, 0.7 * ms)]
        spans.append({"name": "dispatch", "t_start": at - MONO,
                      "t_end": at + 0.2 * ms - MONO, "span_id": 100 + k,
                      "parent_id": 200 + k, "trace_id": "replica-0",
                      "attrs": dict(events[-5]["stats"])})
    spans += [
        {"name": "decode", "trace_id": "req-1", "span_id": 1,
         "parent_id": None, "t_start": -5 * ms - MONO, "t_end": None,
         "attrs": {}},
        {"name": "prefill", "trace_id": "req-2", "span_id": 2,
         "parent_id": None, "t_start": 10 * ms - MONO,
         "t_end": 38.5 * ms - MONO, "attrs": {"uid": 2}},
        {"name": "decode", "trace_id": "req-2", "span_id": 3,
         "parent_id": None, "t_start": 38.5 * ms - MONO,
         "t_end": 60 * ms - MONO, "attrs": {}}]
    return events, spans


def test_decode_time_and_prefill_own_share_by_hand(monkeypatch):
    events, spans = two_requests()
    ctx = Ctx(events, spans, (-MONO, 50e-3 - MONO), monkeypatch=monkeypatch)
    # five one-token forwards of 4 ms; two chunk forwards of 10 ms over
    # 64 + 40 valid tokens
    assert dr.dev_decode_ms_per_forward(ctx) == pytest.approx(4.0)
    assert dr.dev_prefill_us_per_token(ctx) == pytest.approx(20e3 / 104)
    # request 1 is between tokens over the whole window: of its 50 ms, 20
    # are another request's chunks and 10 the device idle
    assert dr.decode_time_share(ctx, "chunk") == pytest.approx(40.0)
    assert dr.decode_time_share(ctx, "idle") == pytest.approx(20.0)
    # request 2 waited 28.5 ms for its first token, 20 of them in forwards
    # of its own
    assert dr.prefill_own_share(ctx) == pytest.approx(100 * 20 / 28.5)
    # idle: 4-5, 9-12, 22-23, 27-28, 38-40, 44-45, 49-50 ms; every forward
    # was handed over half a millisecond before it started
    # (but the last stretch, which no forward follows)
    assert dr.idle_share(ctx, "launch") == pytest.approx(100 * 6 * 0.3 / 50)
    assert dr.idle_share(ctx, "no_work") == 0.0
    for cause in ("starved", "unspanned", "in_module", "launch", "no_work"):
        assert dr.idle_share(ctx, cause) >= 0.0
    assert sum(dr.idle_share(ctx, c) for c in dr.CAUSES) == \
        pytest.approx(20.0)


def test_a_span_the_profiler_cut_off_is_read_from_the_programs_spans(
        monkeypatch):
    """The window ends in an ``idle_wait`` that was still open when the
    trace stopped: no annotation, but the program's span says so."""
    events, spans = two_requests()
    ctx = Ctx(events, spans, (-MONO, 50e-3 - MONO), monkeypatch=monkeypatch)
    # the last millisecond is under no annotation, and no forward follows
    assert dr.idle_share(ctx, "unspanned") > 100 * 0.5 / 50
    assert dr.idle_share(ctx, "no_work") == 0.0
    waiting = {"name": "idle_wait", "trace_id": "replica-0", "span_id": 900,
               "parent_id": None, "t_start": 49.5e-3 - MONO, "t_end": None,
               "attrs": {"open": True}}
    events.append(ann("idle_wait", -1.0, 0.5))      # the name is mirrored
    ctx = Ctx(events, spans + [waiting], (-MONO, 50e-3 - MONO),
              monkeypatch=monkeypatch)
    assert dr.idle_share(ctx, "no_work") == pytest.approx(100 * 0.5 / 50)
    assert sum(dr.idle_share(ctx, c) for c in dr.CAUSES) == \
        pytest.approx(20.0)


def test_a_decode_span_that_covers_half_the_window(monkeypatch):
    events, spans = two_requests()
    spans = [s for s in spans if s["trace_id"] != "req-1"]
    ctx = Ctx(events, spans, (-MONO, 50e-3 - MONO), monkeypatch=monkeypatch)
    # request 2 decodes from 38.5 ms: 11.5 ms, no chunk, 38.5-40, 44-45
    # and 49-50 idle
    assert dr.decode_time_share(ctx, "chunk") == 0.0
    assert dr.decode_time_share(ctx, "idle") == pytest.approx(
        100 * 3.5 / 11.5)


def steps(flags, t0=0.0):
    spans = []
    for k, (dispatched, overlapped, starved) in enumerate(flags):
        spans.append({"name": "step", "span_id": 10 * k, "parent_id": None,
                      "t_start": t0 + k, "t_end": t0 + k + 0.5,
                      "attrs": {"overlapped": overlapped,
                                "starved": starved}})
        if dispatched:
            spans.append({"name": "stage", "span_id": 10 * k + 1,
                          "parent_id": 10 * k, "t_start": t0 + k,
                          "t_end": t0 + k + 0.1, "attrs": {}})
    return spans


def test_steps_share_counts_the_steps_that_dispatched(monkeypatch):
    flags = [(True, False, False), (True, True, False), (True, True, True),
             (False, False, False), (True, True, False),
             (True, True, True)]        # the last one past the window
    ctx = Ctx([], steps(flags), (0.0, 4.5), monkeypatch=monkeypatch)
    assert dr.steps_share(ctx, "overlapped") == pytest.approx(75.0)
    assert dr.steps_share(ctx, "starved") == pytest.approx(25.0)
    # a program from before the attr existed
    for s in ctx.result["program_spans"]:
        s["attrs"].pop("starved", None)
    assert dr.steps_share(ctx, "starved") is None


# ------------------------------------------ the two fwd_*_dev_ms readers

def _summary_of(events):
    """What ``trace.summarize`` keeps for ``readers.forward_device_ms``:
    the probe's ``bench:forward[SxC]`` annotation beside each dispatch,
    and the forward modules."""
    host = [{"name": "bench:forward[{bucket_seqs}x{bucket_chunk}]".format(
        **d["stats"]), "start": d["start"], "dur": d["dur"]}
            for d in dr.dispatches(events)]
    modules = [dict(m, device=DEV) for m in dr.forward_modules(events)]
    return {"window": scopes._window(events), "host": host,
            "modules": modules}


def test_fwd_dev_ms_pairs_by_order_where_the_nearest_annotation_is_the_neighbours(
        monkeypatch):
    """``fwd_mixed_dev_ms`` / ``fwd_decode_dev_ms`` (PR 49, the check's
    refusal: ``trinity-large-preview.mixedctx``, seed 1940209189, printed
    no ``fwd_mixed_dev_ms``). With a step in flight each chunk forward
    starts on the device while the host is already dispatching the next
    one-token step, so by nearness the ``[1, 64]`` tag finds no module at
    all and the 9 ms chunks are booked under ``[4, 1]``."""
    events = simulate(SCHEDULE)
    summary = _summary_of(events)
    assert trace.module_seconds(summary, "forward", tag="1x64") == []
    assert sorted(trace.module_seconds(summary, "forward", tag="4x1"))[-2:] \
        == [9e-3, 9e-3]
    ctx = Ctx(events, [], summary["window"], monkeypatch=monkeypatch)
    ctx.trace = summary
    assert readers.forward_device_ms(ctx, mixed=True) == pytest.approx(9.0)
    assert readers.forward_device_ms(ctx, mixed=False) == pytest.approx(4.0)
    assert dr.forward_device_ms(ctx, mixed=True) == pytest.approx(9.0)
    # the decode bucket is the most frequent one: [4, 1] eight times of
    # 4 ms against [8, 1] once of 5 ms
    slower = simulate([[(8, 1, 5e-3)]] * 3 + [DECODE])
    ctx = Ctx(slower, [], scopes._window(slower), monkeypatch=monkeypatch)
    assert dr.forward_device_ms(ctx, mixed=False) == pytest.approx(5.0)
    assert dr.forward_device_ms(ctx, mixed=True) is None
    # a trace without ds:dispatch cannot be paired by order: read by
    # nearness as before, whatever that finds
    bare = [e for e in events if e["name"] != "ds:dispatch"]
    ctx = Ctx(bare, [], summary["window"], monkeypatch=monkeypatch)
    ctx.trace = summary
    assert dr.forward_device_ms(ctx, mixed=True) is None
    assert readers.forward_device_ms(ctx, mixed=True) is None
    assert readers.forward_device_ms(ctx, mixed=False) == pytest.approx(4.0)


def test_fwd_dev_ms_on_the_recorded_chip_trace(monkeypatch):
    """The recorded stretch of ``qwen3-next-80b-a3b.longdoc``: four
    ``[1, 1024]`` chunk forwards (the ``[1, 512]`` one is narrower and
    not the mixed bucket), five ``[2, 1]`` steps against two ``[4, 1]``."""
    events = scopes.load_recorded(RECORDED)
    ctx = Ctx(events, [], scopes._window(events), monkeypatch=monkeypatch)
    assert dr.forward_device_ms(ctx, mixed=True) == \
        pytest.approx((29.831 + 30.260) / 2, abs=1e-3)
    assert dr.forward_device_ms(ctx, mixed=False) == \
        pytest.approx(1.703, abs=1e-3)


# --------------------------------------------------------- nothing to read

@pytest.mark.parametrize("name", NEW_METRICS)
def test_every_new_metric_returns_none_off_the_chip_and_without_dispatch(
        name, monkeypatch):
    bench_dir = os.path.join(mf.CHECKOUT, "benchmark")
    reader = mf.find_module(bench_dir, "layer_metrics", name)
    events, spans = two_requests()
    off_chip = Ctx(events, spans, (-MONO, 50e-3 - MONO), on_chip=False,
                   monkeypatch=monkeypatch)
    assert reader.reduce(off_chip) is None
    # the parent's program: no ds:dispatch, no dispatch span, no starved
    old = [e for e in events if e["name"] != "ds:dispatch"]
    old_spans = [dict(s, attrs={}) for s in spans if s["name"] != "dispatch"]
    parent = Ctx(old, old_spans + steps([(True, True, False)]),
                 (-MONO, 50e-3 - MONO), monkeypatch=monkeypatch)
    for s in parent.result["program_spans"]:
        s["attrs"].pop("starved", None)
    if name.endswith("steps_overlapped_share"):
        assert reader.reduce(parent) is None or reader.reduce(parent) >= 0
    else:
        assert reader.reduce(parent) is None
    # and with both, a number
    both = Ctx(events, spans + steps([(True, True, False)], t0=-MONO),
               (-MONO, 50e-3 - MONO), monkeypatch=monkeypatch)
    assert isinstance(reader.reduce(both), float)


def test_the_manifest_lists_the_new_metrics_behind_the_old():
    manifest = mf.load()
    mf.validate(manifest)
    names = [m["name"] for m in manifest["per_layer"]]
    # appended behind what PR 36 left last, in one piece; what later PRs
    # append stands behind them
    first = names.index(NEW_METRICS[0])
    assert tuple(names[first:first + len(NEW_METRICS)]) == NEW_METRICS
    assert names[first - 1] == "mla_prefill_roofline"
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW_METRICS:
        m = by_name[name]
        assert (m["moves"] == "serve_tok_s") == name.startswith("sat_")
        assert (m["workloads"] == ["mistral-7b.batch"]) == \
            name.startswith("sat_")


# ------------------------------------------------- a recorded chip trace

RECORDED = os.path.join(mf.CHECKOUT, "benchmark", "testdata",
                        "longdoc_dispatch_steps.json")


def test_the_recorded_chip_trace_pairs_by_order_and_reads_what_the_chip_read():
    """Eight steps of ``qwen3-next-80b-a3b.longdoc`` (my chip run, PR 39,
    seed 3900000024): five puts parted into an ``[S, 1]`` forward and a
    chunk forward, a chunk bucket that narrows, a decode bucket that
    widens. Held: the by-order device times of every forward."""
    events = scopes.load_recorded(RECORDED)
    r = dr.reduce_trace(events)
    assert (r["o0"], r["lead"], r["unpaired_dispatches"]) == (13283, 0, 1)
    got = [(f["ordinal"], f["attrs"]["bucket_seqs"],
            f["attrs"]["bucket_chunk"], f["attrs"]["valid_tokens"],
            round(f["dur"] * 1e3, 3)) for f in r["forwards"]]
    assert got == [
        (13283, 2, 1, 2, 1.693), (13284, 1, 1024, 1024, 29.452),
        (13285, 2, 1, 2, 1.744), (13286, 1, 1024, 1024, 29.831),
        (13287, 2, 1, 2, 1.702), (13288, 1, 1024, 1024, 30.260),
        (13289, 2, 1, 2, 1.751), (13290, 1, 1024, 1024, 30.629),
        (13291, 2, 1, 2, 1.703), (13292, 1, 512, 481, 20.091),
        (13293, 4, 1, 3, 2.059), (13294, 4, 1, 3, 2.050)]
    assert r["programs"] == {
        (2, 1, 0): "18342530735309512537", (1, 1024, 0): "14013446783580413272",
        (1, 512, 0): "14421434743263835294", (4, 1, 0): "7111204716521093792"}
    # the parted puts: two dispatches under one stage, joined by uid to
    # the request they prefill
    stages = [e for e in events if e["name"] == "ds:stage"]
    held = [[d["stats"]["ordinal"] for d in dr.dispatches(events)
             if s["start"] <= d["start"] < dr._end(s)] for s in stages]
    assert held[:5] == [[13283 + 2 * k, 13284 + 2 * k] for k in range(5)]
    assert r["forwards"][1]["attrs"]["uids"] == 93       # one uid: an int
    assert r["forwards"][0]["attrs"]["uids"] == "91 92"
    # what pairing by nearness (trace.module_seconds' rule) makes of it:
    # every 30 ms chunk forward booked under a decode bucket
    found = dr.dispatches(events)
    nearest = [min(found, key=lambda d: abs(d["start"] - m["start"]))
               for m in dr.forward_modules(events)]
    assert [d["stats"]["bucket_chunk"] for d in nearest] == [1] * 12
    # the clock: this stretch bounds it to a millisecond either way
    clk = r["clock"]
    assert clk["offset_s"] == pytest.approx(-672.9e-6, abs=1e-7)
    assert clk["uncertainty_s"] == pytest.approx(1042.2e-6, abs=1e-7)
    # idle by cause sums to what scopes.summarize calls idle
    idle = r["idle"]
    assert sum(idle["by_cause"].values()) == pytest.approx(
        scopes.summarize(events)["idle_s"], rel=1e-9)
    assert max(idle["by_cause"], key=idle["by_cause"].get) == "starved"
    assert idle["starved_by_phase"][0][0] == "ds:dispatch"
    text = dr.describe(events)
    assert "12 forward modules in the window, 0 unpaired" in text
    assert "[1, 1024] -> 14013446783580413272: 4, 30.046" in text
