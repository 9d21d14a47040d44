#!/usr/bin/env python3
"""Name each idle gap of the device by the program span under it.

``repro.obs`` records the program's spans on the host's monotonic clock,
in microseconds from the tracer's epoch.  The device profiler records on a
clock of its own, in nanoseconds from the profile's start.
``repro.obs.trace.profile_window`` marks one interval on both clocks: the
annotation ``xplane.WINDOW`` in the profile, and a ``profile/window`` span
in the tracer.  ``ClockMap`` maps the span's ``[ts, ts + dur]`` linearly
onto the annotation's ``[start, end]``.  It raises where the two durations
differ by more than ``MISFIT_LIMIT_US``.

``split`` sweeps the device's idle intervals.  At each instant it gives
the interval to the highest-ranked program span open on any thread
(``RANKS``, first wins; between spans of one rank, the one opened first).
An instant under no program span is ``runtime``: the host is then polling
completions, dispatching continuations, releasing dependencies or
scheduling workers.  The parts sum to the idle time.

Run as a script, it makes one traced run of a cell as ``bench/run.py
--trace 1`` does: the same program, traffic, ``bench.*`` annotations,
tracer and slice placement.  Each slice is opened by ``profile_window``
and lasts ``SLICE_S``.  It prints one JSON line: each slice's clock
misfit, the idle time by span, ``idle_dispatch_share``,
``idle_runtime_share`` and ``slots_in_flight``, the part of ``runtime``
with no request in flight (``idle_no_request_s``: in an open loop the
engine then waits for arrivals), and the cell's end-to-end metrics read
under tracing, to set beside a ``--trace 0`` run of the same seed.
Without a TPU it exits with 3.

  python bench/attribute.py --workload granite-3-2b.chat --seed 7 \\
      --seconds 51
"""

from __future__ import annotations

import dataclasses
import heapq
import json
import os
import sys
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import xplane

# The most by which the annotation's duration and the ``profile/window``
# span's may differ in one slice: above the largest reading over nine slices
# on a v5e (106 us), and under a third of the device's idle time per decode
# step in the batch cell (about 1.3 ms).
MISFIT_LIMIT_US = 500.0

# Which program spans claim an idle instant, highest first.
RANKS = {("host", "gc"): 0, ("host", "compile"): 1,
         ("serving", "pad_cache"): 2, ("serving", "device_step"): 3,
         ("serving", "detok"): 4, ("task", "pause"): 5, ("task", "run"): 6,
         ("bench", "no_request"): 7}
RUNTIME = "runtime"
# The script's marks of the intervals in which no request was in flight
# (``no_request``): under every program span, so that they split
# ``runtime`` in two, the host idle and the host between steps.
NO_REQUEST = "bench/no_request"
# The parts that are the host dispatching a model step.
DISPATCH = ("dispatch.prefill", "dispatch.decode", "serving/pad_cache")

# How long a slice of the script's traced run lasts: the harness's
# TRACE_SLICE_S, and about a prefill's time to its first token more.
SLICE_S = 1.5
INFLIGHT = "serving.inflight_at_dispatch"

Span = Tuple[float, float, int, str]      # start, end (ns), rank, part


class ClockMisfit(RuntimeError):
    """The profile's annotation and the tracer's span of one slice differ
    in duration by more than the limit."""

    def __init__(self, misfit_us: float, limit_us: float) -> None:
        super().__init__(f"the trace's window lasts {misfit_us:+.1f} us "
                         f"more than the tracer's profile/window span "
                         f"(limit {limit_us} us)")
        self.misfit_us = misfit_us


@dataclasses.dataclass
class ClockMap:
    """Tracer microseconds to trace nanoseconds within one slice."""

    ts_us: float
    dur_us: float
    start_ns: int
    end_ns: int
    limit_us: float = MISFIT_LIMIT_US

    def __post_init__(self) -> None:
        self.misfit_us = (self.end_ns - self.start_ns) * 1e-3 - self.dur_us
        if not abs(self.misfit_us) <= self.limit_us:
            raise ClockMisfit(self.misfit_us, self.limit_us)
        self.ns_per_us = (self.end_ns - self.start_ns) / self.dur_us

    def __call__(self, t_us: float) -> float:
        return self.start_ns + (t_us - self.ts_us) * self.ns_per_us


def part(ev: Dict[str, Any]) -> Optional[Tuple[int, str]]:
    """(rank, part name) of a tracer event that can claim idle time."""
    key = (ev.get("cat"), ev.get("name"))
    if ev.get("ph") != "X" or key not in RANKS:
        return None
    if key == ("serving", "device_step"):
        return RANKS[key], f"dispatch.{ev['args']['kind']}"
    if key == ("task", "run"):
        return RANKS[key], "task." + ev["args"]["task"].split(":", 1)[0]
    return RANKS[key], "/".join(key)


def no_request(events: Sequence[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """``bench``/``no_request`` spans (tracer us) over the intervals in
    which no request was in flight: each request is in flight from its
    admission (the end of its ``serving/queued`` span) to the end of its
    ``finish`` task."""
    start: Dict[int, float] = {}
    end: Dict[int, float] = {}
    for ev in events:
        if ev["ph"] != "X":
            continue
        if (ev["cat"], ev["name"]) == ("serving", "queued"):
            start.setdefault(ev["args"]["rid"], ev["ts"] + ev["dur"])
        elif (ev["cat"], ev["name"]) == ("task", "run") \
                and ev["args"]["task"].startswith("finish:"):
            end[int(ev["args"]["task"].split(":", 1)[1])] = \
                ev["ts"] + ev["dur"]
    lo = min((ev["ts"] for ev in events), default=0.0)
    hi = max((ev["ts"] + ev.get("dur", 0.0) for ev in events), default=0.0)
    busy = xplane._union([(s, end.get(rid, hi)) for rid, s in start.items()])
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    return [{"ph": "X", "cat": "bench", "name": "no_request", "ts": a,
             "dur": b - a, "pid": 0, "tid": 0, "args": {}}
            for a, b in zip(edges[::2], edges[1::2]) if b > a]


def idle_intervals(planes: Sequence[Any]):
    """The ``xplane.WINDOW`` annotation's (start, end), and for each device
    the intervals inside it in which no program ran (trace ns), counted as
    ``xplane.reduce_planes`` counts busy time."""
    window = None
    for plane in planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == xplane.WINDOW:
                    s = int(ev.start_ns)
                    window = (s, s + int(ev.duration_ns))
    if window is None:
        raise RuntimeError(f"the trace holds no {xplane.WINDOW!r} annotation")
    lo, hi = window
    idle = []
    for dev in planes:
        if not dev.name.startswith("/device:TPU:"):
            continue
        runs = []
        for line in dev.lines:
            if line.name != "XLA Modules":
                continue
            for ev in line.events:
                s = int(ev.start_ns)
                e = s + int(ev.duration_ns)
                if e > lo and s < hi:
                    runs.append(xplane._clip(s, e, lo, hi))
        edges = [lo] + [x for iv in xplane._union(runs) for x in iv] + [hi]
        idle.append([(s, e) for s, e in zip(edges[::2], edges[1::2])
                     if e > s])
    if not idle:
        raise RuntimeError("the trace holds no TPU device plane")
    return window, idle


def split(idle: Sequence[Tuple[int, int]],
          spans: Sequence[Span]) -> Dict[str, float]:
    """Nanoseconds of ``idle`` (sorted, disjoint intervals) by the part
    that claims them."""
    spans = sorted(sp for sp in spans if sp[1] > sp[0])
    out: Dict[str, float] = {}
    open_: List[Tuple[int, float, float, str]] = []  # rank, start, end, part
    i = 0
    for a, b in idle:
        t = a
        while t < b:
            while i < len(spans) and spans[i][0] <= t:
                s, e, rank, name = spans[i]
                heapq.heappush(open_, (rank, s, e, name))
                i += 1
            while open_ and open_[0][2] <= t:
                heapq.heappop(open_)
            nxt = b if i == len(spans) else min(b, spans[i][0])
            name = RUNTIME
            if open_:
                nxt = min(nxt, open_[0][2])
                name = open_[0][3]
            out[name] = out.get(name, 0.0) + (nxt - t)
            t = nxt
    return out


def attribute(planes: Sequence[Any], window: Dict[str, Any],
              events: Sequence[Dict[str, Any]],
              limit_us: float = MISFIT_LIMIT_US) -> Dict[str, Any]:
    """One slice: the device's idle seconds by part (averaged over the
    devices), given its profile and the tracer's events, ``window`` being
    the ``profile/window`` span that opened it."""
    (lo, hi), idle = idle_intervals(planes)
    clock = ClockMap(window["ts"], window["dur"], lo, hi, limit_us)
    spans: List[Span] = []
    for ev in events:
        p = part(ev)
        if p is None:
            continue
        s, e = clock(ev["ts"]), clock(ev["ts"] + ev["dur"])
        if e > lo and s < hi:
            spans.append((max(s, lo), min(e, hi)) + p)
    parts: Dict[str, float] = {}
    for intervals in idle:
        for name, ns in split(intervals, spans).items():
            parts[name] = parts.get(name, 0.0) + ns * 1e-9 / len(idle)
    return {"window_s": (hi - lo) * 1e-9,
            "idle_s": sum(e - s for iv in idle for s, e in iv) * 1e-9
            / len(idle),
            "idle_by_span": parts, "misfit_us": clock.misfit_us,
            "enter_us": window["args"]["enter_us"],
            "exit_us": window["args"]["exit_us"]}


def merge(slices: Sequence[Dict[str, Any]]) -> Dict[str, Any]:
    """The slices as one traced window: idle by part, largest first, and
    the shares of the traced time under a dispatch and under no span.
    ``NO_REQUEST`` counts as ``runtime`` here, and is given apart as
    ``idle_no_request_s``."""
    window_s = sum(sl["window_s"] for sl in slices)
    parts: Dict[str, float] = {}
    for sl in slices:
        for name, s in sl["idle_by_span"].items():
            parts[name] = parts.get(name, 0.0) + s
    no_req = parts.pop(NO_REQUEST, 0.0)
    if no_req:
        parts[RUNTIME] = parts.get(RUNTIME, 0.0) + no_req
    return {"window_s": window_s, "idle_no_request_s": no_req,
            "idle_s": sum(sl["idle_s"] for sl in slices),
            "idle_by_span": sorted(parts.items(), key=lambda kv: -kv[1]),
            "idle_dispatch_share": 100.0 * sum(parts.get(n, 0.0)
                                               for n in DISPATCH) / window_s,
            "idle_runtime_share": 100.0 * parts.get(RUNTIME, 0.0) / window_s}


# ---------------------------------------------------------------------------
# the script: one traced run of a cell
# ---------------------------------------------------------------------------
def _profile(t0: float, plan: Any, out: Dict[str, Any], registry) -> None:
    """Slices of the window, placed as the harness places them, each opened
    by ``profile_window``; the registry reset at the window's start and
    the requests in flight at each dispatch read at its end."""
    import jax
    import harness
    from repro.obs import trace as obs_trace
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        W0, W1 = plan.window
        part_s = (W1 - W0) / harness.TRACE_SLICES
        time.sleep(max(0.0, t0 + W0 - time.monotonic()))
        registry.reset()
        for i in range(harness.TRACE_SLICES):
            middle = W0 + (i + 0.5) * part_s - SLICE_S / 2
            lo = harness.slice_start(max(middle, time.monotonic() - t0 + 0.5),
                                     plan.arrivals)
            if lo + SLICE_S > W1:
                break
            log_dir = os.path.join(out["tmp"], str(i))
            time.sleep(max(0.0, t0 + lo - 0.5 - time.monotonic()))
            jax.profiler.start_trace(log_dir, profiler_options=opts)
            time.sleep(max(0.0, t0 + lo - time.monotonic()))
            with obs_trace.profile_window(xplane.WINDOW):
                time.sleep(SLICE_S)
            b = time.monotonic()
            jax.profiler.stop_trace()
            out["slices"].append({"file": xplane.find(log_dir),
                                  "stop_s": time.monotonic() - b})
        time.sleep(max(0.0, t0 + W1 - time.monotonic()))
        out["inflight"] = registry.histogram(INFLIGHT, base=1.0).summary()
    except Exception as exc:   # reported by main
        out["error"] = repr(exc)


def main(argv=None) -> int:
    import argparse
    import jax
    from jax.profiler import ProfileData

    import harness
    import traffic
    from repro.launch.cache import enable_compile_cache
    from repro.obs import REGISTRY
    from repro.obs import trace as obs_trace

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    args = p.parse_args(argv)
    cell = harness.load_cell(args.workload)
    try:
        device = harness.device_info(int(cell.entry["chips"]))
    except harness.NoChip as exc:
        print(f"[attribute] {exc}", file=sys.stderr)
        return 3
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    plan = traffic.plan(cell.mix, cell.params, args.seed, args.seconds)
    program = harness.build(cell, seed=args.seed, n_prompts=plan.n_prompts,
                            max_output=plan.max_output, annotate=True)
    tracer = obs_trace.Tracer(capacity=harness.OBS_CAPACITY)
    obs_trace.set_tracer(tracer)
    with tempfile.TemporaryDirectory(prefix="bench-attribute-") as tmp:
        out: Dict[str, Any] = {"tmp": tmp, "slices": []}
        t0 = time.monotonic()
        prof = threading.Thread(target=_profile, daemon=True,
                                args=(t0, plan, out, REGISTRY))
        prof.start()
        served = harness.serve(program, plan, trace=False)
        prof.join(timeout=harness.TRACE_STOP_S)
        obs_trace.set_tracer(None)
        events = tracer.events()
        if prof.is_alive() or "error" in out or not out["slices"]:
            raise RuntimeError(f"tracing failed: {out.get('error')}")
        if not served.finished or served.failure:
            raise RuntimeError(f"the engine did not finish: "
                               f"{[repr(e) for e in served.failure]}")
        windows = [ev for ev in events
                   if ev["cat"] == "profile" and ev["name"] == "window"]
        marks = no_request(events)
        if len(windows) != len(out["slices"]):
            raise RuntimeError(f"{len(out['slices'])} slices, "
                               f"{len(windows)} profile/window spans")
        slices, refused = [], []
        for sl, win in zip(out["slices"], windows):
            planes = list(ProfileData.from_file(sl["file"]).planes)
            link = {"enter_us": win["args"]["enter_us"],
                    "exit_us": win["args"]["exit_us"],
                    "stop_s": sl["stop_s"]}
            try:
                one = attribute(planes, win, events + marks)
            except ClockMisfit as exc:
                refused.append(dict(link, misfit_us=exc.misfit_us))
                continue
            one.update(link, busy_s=xplane.reduce_planes(planes)["busy_s"])
            slices.append(one)
        if not slices:
            raise RuntimeError(f"every slice's clock link was refused: "
                               f"{refused}")
    run = harness.Run(
        cell=cell, seconds=args.seconds, window=plan.window, end=served.end,
        requests=served.requests, withdrawn=served.withdrawn,
        emits=served.emits, setup_s=0.0, prompt_len=plan.prompt_len,
        cache_len=plan.prompt_len + plan.max_output,
        peak=harness.peaks(device["kind"]), ref=None)
    traced = {m["name"]: harness.reader(m["name"]).read(run, m)
              for m in cell.end_to_end if m["name"] != "setup_s"}
    whole = merge(slices)
    inflight = out["inflight"]
    result = {
        "workload": cell.name, "seed": args.seed, "device": device,
        "engine_start_after_s": served.t0 - t0,
        "events": len(events),
        "slices": [{k: sl[k] for k in ("window_s", "idle_s", "busy_s",
                                       "misfit_us", "enter_us", "exit_us",
                                       "stop_s")} for sl in slices],
        "slices_refused": refused,
        "idle_share": 100.0 * whole["idle_s"] / whole["window_s"],
        "idle_share_from_busy": 100.0 * (1.0 - sum(
            sl["busy_s"] for sl in slices) / whole["window_s"]),
        "idle_by_span": whole["idle_by_span"],
        "idle_no_request_s": whole["idle_no_request_s"],
        "idle_dispatch_share": whole["idle_dispatch_share"],
        "idle_runtime_share": whole["idle_runtime_share"],
        "slots_in_flight": (inflight["sum"] / inflight["count"]
                            if inflight["count"] else None),
        "dispatches": inflight["count"],
        "traced_end_to_end": traced,
    }
    sys.stdout.write(json.dumps(result) + "\n")
    return 0


if __name__ == "__main__":
    ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [os.path.join(ROOT, "src")]
    sys.exit(main())
