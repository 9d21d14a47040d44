"""Observability layer (repro.obs): tracing, metrics, overlap accounting.

Covers the tracer itself (bounded per-thread rings, thread safety, the
NullTracer disabled path), the exported Chrome-trace document (schema
validation, file round-trip), the metrics registry (striped counters with
exact totals, gauges, histograms), the overlap-fraction oracle — the
discrete-event simulator replays a known scenario whose overlap is exact
and the host tracer reproduces the same number within tolerance on the
same structure — and the deprecation shims left behind by the
``serving.metrics`` → ``repro.obs.metrics`` move.
"""

import gc
import json
import threading
import time
import warnings

import pytest

from repro import obs
from repro.core import TaskRuntime, tac
from repro.core import simulate
from repro.core.simulate import (COMM_EVENTS, COMM_PAUSED, COMPUTE,
                                 SimTask, Simulator)
from repro.obs import (Counter, Gauge, Histogram, MetricsRegistry,
                       NullTracer, Tracer, overlap_fraction,
                       per_rank_overlap, straggler_scores, summarize)
from repro.obs import trace as trace_mod


@pytest.fixture(autouse=True)
def _tracing_off():
    """Every test starts and ends with tracing disabled (the default)."""
    prev = trace_mod.set_tracer(None)
    yield
    trace_mod.set_tracer(prev if not isinstance(prev, NullTracer) else None)


# ---------------------------------------------------------------------------
# the tracer
# ---------------------------------------------------------------------------
def test_tracing_disabled_by_default():
    assert trace_mod.TRACING is False
    assert isinstance(trace_mod.get_tracer(), NullTracer)
    # NullTracer methods are no-ops with an empty event list
    nt = NullTracer()
    nt.span("task", "run", 0.0, 1.0, rank=0)
    nt.instant("task", "submit")
    assert nt.events() == []


def test_tracing_context_installs_and_restores():
    assert not trace_mod.TRACING
    with obs.tracing() as tr:
        assert trace_mod.TRACING
        assert trace_mod.get_tracer() is tr
        tr.instant("task", "submit", task="t")
        assert len(tr.events()) == 1
    assert not trace_mod.TRACING


def test_ring_buffer_is_bounded_and_keeps_newest():
    tr = Tracer(capacity=8)
    for i in range(100):
        tr.instant("task", "submit", t=float(i), seq=i)
    evs = tr.events()
    assert len(evs) == 8
    assert [e["args"]["seq"] for e in evs] == list(range(92, 100))


def test_tracer_rejects_zero_capacity():
    with pytest.raises(ValueError):
        Tracer(capacity=0)


def test_tracer_thread_safety_distinct_tids():
    tr = Tracer()
    n_threads, per = 8, 200

    def emit(i):
        for k in range(per):
            tr.instant("task", "submit", t=float(i * per + k), worker=i)

    threads = [threading.Thread(target=emit, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    evs = tr.events()
    assert len(evs) == n_threads * per
    # one ring (hence one tid) per emitting thread; events are merged
    # sorted by timestamp
    assert len({e["tid"] for e in evs}) == n_threads
    assert [e["ts"] for e in evs] == sorted(e["ts"] for e in evs)
    tr.clear()
    assert tr.events() == []


def test_span_event_rank_attribution():
    ev = trace_mod.span_event("task", "run", 10.0, 5.0, rank=3, task="t")
    assert ev["pid"] == 3 and ev["args"]["rank"] == 3
    assert ev["ts"] == 10.0 and ev["dur"] == 5.0
    un = trace_mod.span_event("task", "run", 0.0, 1.0)
    assert un["pid"] == 0 and "rank" not in un["args"]


# ---------------------------------------------------------------------------
# export + validation
# ---------------------------------------------------------------------------
def test_export_roundtrip_and_validation(tmp_path):
    tr = Tracer()
    t0 = time.monotonic()
    tr.span("task", "run", t0, t0 + 0.01, rank=1, task="a",
            label="compute")
    tr.span("handle", "inflight", t0, t0 + 0.02, rank=1, kind="Event")
    tr.instant("continuation", "dispatch")
    path = tmp_path / "out.json"
    doc = obs.export_trace(str(path), tracer=tr, extra={"leg": "test"})
    loaded = json.loads(path.read_text())
    assert loaded["otherData"]["leg"] == "test"
    assert obs.validate_trace(loaded) == []
    assert obs.validate_trace(doc) == []
    obs.assert_valid_trace(loaded)


def test_validation_catches_schema_violations():
    bad = [
        {"ph": "X", "cat": "task", "name": "nope", "ts": 0.0, "dur": 1.0,
         "pid": 0, "tid": 0, "args": {}},                 # unknown span name
        {"ph": "i", "s": "t", "cat": "bogus", "name": "submit", "ts": 0.0,
         "pid": 0, "tid": 0, "args": {}},                 # unknown category
        {"ph": "X", "cat": "task", "name": "run", "ts": 0.0, "dur": -1.0,
         "pid": 0, "tid": 0, "args": {}},                 # negative duration
        {"ph": "Z", "name": "x", "ts": 0.0, "pid": 0, "tid": 0},  # bad ph
        {"ph": "i", "s": "t", "cat": "serving", "name": "token", "ts": 0.0,
         "pid": 0, "tid": 0, "args": {}},                 # removed instant
        {"ph": "C", "name": "queue", "ts": 0.0, "pid": 0, "tid": 0,
         "args": {"queue": 3.0}},                         # removed counters
    ]
    problems = obs.validate_trace(bad)
    assert len(problems) == 6
    with pytest.raises(ValueError):
        obs.assert_valid_trace(bad)
    assert obs.validate_trace({"nope": 1}) \
        == ["document has no 'traceEvents' list"]


# ---------------------------------------------------------------------------
# the profiler's clock, garbage collections, compilations
# ---------------------------------------------------------------------------
def test_profile_window_records_one_span():
    with obs.profile_window("untraced"):      # no tracer, no profile
        pass
    with obs.tracing() as tr:
        with obs.profile_window("bench.traced_window"):
            time.sleep(0.01)
        wins = [e for e in tr.events() if e["cat"] == "profile"]
    assert obs.validate_trace(wins) == []
    assert len(wins) == 1
    w = wins[0]
    assert w["name"] == "window"
    assert w["args"]["annotation"] == "bench.traced_window"
    assert w["dur"] >= 10_000
    assert 0 <= w["args"]["enter_us"] + w["args"]["exit_us"] < w["dur"]


def test_gc_hook_comes_and_goes_with_the_tracer():
    enabled = gc.isenabled()
    gc.disable()                    # no automatic collection in between
    try:
        with obs.tracing() as tr:
            gc.collect()
            spans = [e for e in tr.events() if e["cat"] == "host"]
        gc.collect()
        after = [e for e in tr.events() if e["cat"] == "host"]
    finally:
        if enabled:
            gc.enable()
    assert len(spans) == 1 and spans[0]["name"] == "gc"
    assert spans[0]["args"]["generation"] == 2
    assert spans[0]["args"]["collected"] >= 0
    assert obs.validate_trace(spans) == []
    assert after == spans
    assert tr._on_gc not in gc.callbacks


def test_compile_listener_records_backend_compiles():
    import jax
    import jax.numpy as jnp
    k = float(time.monotonic_ns() % 1_000_003)   # a program not yet built
    with obs.tracing() as tr:
        jax.jit(lambda x: x * 3.0 + k)(jnp.ones(7)).block_until_ready()
        spans = [e for e in tr.events()
                 if e["cat"] == "host" and e["name"] == "compile"]
    assert spans
    assert all(e["args"]["event"].endswith("backend_compile_duration")
               for e in spans)
    assert obs.validate_trace(spans) == []


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------
def test_counter_exact_totals_across_threads():
    c = Counter("c")
    n_threads, per = 8, 5000

    def worker():
        for _ in range(per):
            c.inc()

    threads = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # striped cells: no lock on inc, yet the total is EXACT
    assert c.value == n_threads * per
    c.reset()
    assert c.value == 0


def test_gauge_levels_and_high_water():
    g = Gauge("g")
    g.inc(); g.inc(); g.inc()
    assert g.value == 3.0 and g.high_water == 3.0
    g.dec(2.0)
    assert g.value == 1.0 and g.high_water == 3.0
    g.set(10.0)
    assert g.high_water == 10.0
    g.reset()
    assert g.value == 0.0 and g.high_water == 0.0


def test_histogram_summary():
    h = Histogram("h")
    for x in (1e-6, 2e-6, 1e-3, 0.5):
        h.observe(x)
    s = h.summary()
    assert s["count"] == 4.0
    assert s["min"] == 1e-6 and s["max"] == 0.5
    assert abs(h.mean - (1e-6 + 2e-6 + 1e-3 + 0.5) / 4) < 1e-12


def test_registry_shares_by_name_and_type_checks():
    reg = MetricsRegistry()
    assert reg.counter("a") is reg.counter("a")
    with pytest.raises(TypeError):
        reg.gauge("a")
    reg.gauge("b").set(2.0)
    reg.histogram("c").observe(0.1)
    snap = reg.as_dict()
    assert snap["a"]["value"] == 0.0
    assert snap["b"] == {"value": 2.0, "high_water": 2.0}
    assert snap["c"]["count"] == 1.0
    reg.reset()
    assert reg.as_dict()["b"]["value"] == 0.0


# ---------------------------------------------------------------------------
# the overlap-fraction oracle: simulator exact, host within tolerance
# ---------------------------------------------------------------------------
def _oracle_sim_tasks():
    """Rank 0: a 0.2 s compute task beside a comm window closing at 0.5 s.

    With two workers both start at t=0: inflight = [0, 0.5], compute =
    [0, 0.2] — overlap fraction exactly 0.4.
    """
    a = SimTask(0, 0, 0.2, kind=COMPUTE, name="compute")
    b = SimTask(1, 0, 0.0, kind=COMM_EVENTS, event_deps=[(0, 0.3)],
                name="comm")
    return [a, b]


def test_sim_overlap_oracle_exact():
    tasks = _oracle_sim_tasks()
    Simulator(1, 2).run(tasks)
    evs = simulate.trace_events(tasks)
    assert obs.validate_trace(evs) == []
    assert overlap_fraction(evs) == pytest.approx(0.4)
    assert per_rank_overlap(evs) == {0: pytest.approx(0.4)}


def test_host_tracer_overlap_matches_sim_within_tolerance():
    """The same structure on the real runtime: one 0.2 s compute task
    beside an EventHandle posted at ~0 and completed at ~0.5 s.  The
    host number must land near the simulator's exact 0.4."""
    tac.init(tac.TASK_MULTIPLE)
    with obs.tracing() as tr:
        with TaskRuntime(num_workers=2) as rt:
            t0 = time.monotonic()
            box = {}

            def comm():
                box["h"] = tac.EventHandle()

            def compute():
                time.sleep(0.2)

            rt.submit(comm, name="comm", label="comm", rank=0)
            rt.submit(compute, name="compute", label="compute", rank=0)
            rt.taskwait()
            time.sleep(max(0.0, t0 + 0.5 - time.monotonic()))
            box["h"].complete(None)   # closes the inflight span at ~0.5 s
        evs = tr.events()
    assert obs.validate_trace(evs) == []
    host = overlap_fraction(evs, rank=0)
    assert host == pytest.approx(0.4, abs=0.15)


def test_sim_segmented_ring_overlaps_more_than_unsegmented():
    """The segmented schedule's pipelining claim, read off the replayed
    timeline: with combines costing γ > 0, transport of later segments
    hides under combines of earlier ones."""
    from repro.core.schedule import build

    def run(segments):
        sched = build("allreduce", "ring", 4, segments=segments)
        tasks = simulate.schedule_tasks(sched, size=1.0, alpha=1e-3,
                                        beta=1e-2, gamma=1e-2)
        Simulator(4, 1).run(tasks)
        evs = simulate.trace_events(tasks)
        assert obs.validate_trace(evs) == []
        return overlap_fraction(evs)

    assert run(4) > run(1)


def test_sim_paused_task_emits_pause_span():
    a = SimTask(0, 0, 0.1, kind=COMPUTE, name="src")
    b = SimTask(1, 0, 0.05, kind=COMM_PAUSED, event_deps=[(0, 0.2)],
                name="wait")
    Simulator(1, 2, resume_overhead=0.01).run([a, b])
    evs = simulate.trace_events([a, b])
    assert obs.validate_trace(evs) == []
    pauses = [e for e in evs if e["ph"] == "X" and e["cat"] == "task"
              and e["name"] == "pause"]
    assert len(pauses) == 1
    assert pauses[0]["args"]["source"] == "sim"


# ---------------------------------------------------------------------------
# straggler accounting: deterministic injected straggler
# ---------------------------------------------------------------------------
def _straggler_tasks(slow_rank=0, factor=3.0, n_ranks=4, per_rank=2):
    tasks = []
    for r in range(n_ranks):
        for k in range(per_rank):
            dur = 0.5 * (factor if r == slow_rank else 1.0)
            tasks.append(SimTask(len(tasks), r, dur, kind=COMPUTE,
                                 name=f"w[{r},{k}]"))
    return tasks


def test_straggler_scores_flag_injected_straggler():
    tasks = _straggler_tasks()
    Simulator(4, 1).run(tasks)
    evs = simulate.trace_events(tasks)
    scores = straggler_scores(evs)
    assert set(scores) == {0, 1, 2, 3}
    assert scores[0]["score"] == pytest.approx(3.0)
    for r in (1, 2, 3):
        assert scores[r]["score"] == pytest.approx(1.0)
        assert scores[r]["tasks"] == 2.0
    s = summarize(evs)
    assert s["ranks"] == [0, 1, 2, 3]
    assert s["straggler_scores"][0]["score"] == pytest.approx(3.0)


def test_straggler_table_renders_injected_straggler():
    from benchmarks.report import straggler_table

    tasks = _straggler_tasks()
    Simulator(4, 1).run(tasks)
    table = straggler_table(simulate.trace_events(tasks))
    lines = table.splitlines()
    assert lines[0].startswith("| rank ")
    assert len(lines) == 2 + 4            # header + divider + 4 ranks
    assert "| 0 |" in lines[2] and "3.00" in lines[2]


# ---------------------------------------------------------------------------
# host instrumentation end to end: pause spans + deferred release
# ---------------------------------------------------------------------------
def test_host_blocking_wait_emits_pause_span():
    tac.init(tac.TASK_MULTIPLE)
    with obs.tracing() as tr:
        with TaskRuntime(num_workers=2) as rt:
            h = tac.EventHandle()

            def waiter():
                tac.wait(h)       # §4.1: pauses the task, not the core

            rt.submit(waiter, name="waiter", label="comm", rank=0)
            time.sleep(0.05)
            h.complete(42)
            rt.taskwait()
        evs = tr.events()
    assert obs.validate_trace(evs) == []
    counts = summarize(evs)["counts"]
    assert counts.get("task/pause[X]", 0) >= 1
    assert counts.get("handle/inflight[X]", 0) >= 1
    assert counts.get("task/run[X]", 0) >= 1


def test_host_iwait_emits_bind_and_dep_release():
    tac.init(tac.TASK_MULTIPLE)
    with obs.tracing() as tr:
        with TaskRuntime(num_workers=2) as rt:
            h = tac.EventHandle()

            def binder():
                tac.iwait(h)      # §4.3: release deferred to completion

            rt.submit(binder, name="binder", label="comm", rank=1)
            time.sleep(0.05)
            h.complete(7)
            rt.taskwait()
        evs = tr.events()
    counts = summarize(evs)["counts"]
    assert counts.get("handle/bind[i]", 0) == 1
    assert counts.get("handle/dep-release[i]", 0) == 1
    assert counts.get("continuation/dispatch[i]", 0) >= 1


# ---------------------------------------------------------------------------
# deprecation shims: serving.metrics -> repro.obs.metrics
# ---------------------------------------------------------------------------
def test_serving_metrics_shim_warns():
    import repro.serving.metrics as sm

    for name in ("percentile", "TokenRecord", "MetricSink"):
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            moved = getattr(sm, name)
        assert moved is getattr(obs, name)
        assert len(caught) == 1
        assert issubclass(caught[0].category, DeprecationWarning)
        assert "repro.obs" in str(caught[0].message)
    with pytest.raises(AttributeError):
        sm.does_not_exist


def test_serving_package_reexport_is_warning_free():
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        from repro.serving import MetricSink, TokenRecord, percentile
    assert percentile is obs.percentile
    assert TokenRecord is obs.TokenRecord
    assert MetricSink is obs.MetricSink


def test_percentile_and_sink_semantics_preserved():
    assert obs.percentile([5.0, 1.0, 3.0], 50) == 3.0
    with pytest.raises(ValueError):
        obs.percentile([], 99)
    sink = obs.MetricSink()
    rec = obs.TokenRecord(rid=1, step=0, t_submit=1.0, t_emit=1.5)
    sink.emit(rec)
    assert sink.records == [rec]
    assert rec.latency_s == pytest.approx(0.5)
