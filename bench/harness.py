"""The benchmark's harness: one run of one cell, driven by data.

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration, found in ``bench/configs/<config>.json``, and a traffic mix,
found in ``bench/mixes/<traffic>.json``; ``bench/cells/<workload>.json``
holds what belongs to the pair (the offered rate, the correctness limit).
Each metric is read by ``bench/metrics/<name>.py``, where ``<name>`` is the
metric's name up to its first dot.  The reference forward of a
configuration is ``bench/refs/<reference>.py``.  So a new cell, mix,
configuration or metric is new files and entries, never an edit here.

One run: check the device; make the weights and the prompts from the seed
on the device; build the program's ``LMAdapter`` over them and compile and
warm its prefill and decode; drive ``ServingEngine.run`` on its default
(event) leg with the warm-up and then ``--seconds`` of the cell's traffic;
read the metrics; free the program's state; compare what it served with
the float32 reference; print the result.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import json
import math
import os
import pathlib
import sys
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import check
import traffic
import xplane

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
# What a --trace 1 run traces on the device: TRACE_SLICES slices, one near
# the middle of each equal part of the window, so that the reading is not
# that of one moment of an open loop.  In an open loop a slice starts
# TRACE_LEAD_S before a planned arrival.  A slice lasts TRACE_SLICE_S, and
# then on until a request admitted inside it has emitted its first token,
# so that it holds a whole prefill, but no longer than TRACE_SLICE_MAX_S.
# A decode step puts some 1,700 operations into the trace, so a slice is
# kept short enough that stopping the profiler and reading its trace take
# seconds.  The host's own tracer and counters cover the whole window.
TRACE_SLICES = 4
TRACE_SLICE_S = 1.0
TRACE_SLICE_MAX_S = 4.0
TRACE_LEAD_S = 0.25
# How long a run may wait past the window's close for its last requests.
DRAIN_S = 120.0
# How long stopping the device profiler (which writes the trace) may take.
TRACE_STOP_S = 150.0
# Events each thread's ring of the runtime's tracer keeps in a traced run.
OBS_CAPACITY = 1 << 21


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def read_json(path: pathlib.Path) -> Dict[str, Any]:
    with open(path) as fh:
        return json.load(fh)


@dataclasses.dataclass
class Cell:
    name: str
    entry: Dict[str, Any]
    conf: Dict[str, Any]
    mix: Dict[str, Any]
    params: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    @property
    def mc(self) -> Dict[str, Any]:
        return self.conf["model"]


def _applies(metric: Dict[str, Any], cell: str, e2e_names: List[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in e2e_names


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    spec = read_json(root / "BENCHMARK.json")
    entries = {w["name"]: w for w in spec["workloads"]}
    if name not in entries:
        raise SystemExit(f"unknown workload {name!r}; one of "
                         f"{sorted(entries)}")
    entry = entries[name]
    conf_entry = {c["name"]: c for c in spec["configs"]}[entry["config"]]
    conf = read_json(root / conf_entry["file"])
    mix = read_json(root / "bench" / "mixes" / f"{entry['traffic']}.json")
    cell_file = root / "bench" / "cells" / f"{name}.json"
    params = read_json(cell_file) if cell_file.exists() else {}
    e2e = [m for m in spec["end_to_end"] if _applies(m, name, [])]
    names = [m["name"] for m in e2e]
    per_layer = [m for m in spec["per_layer"] if _applies(m, name, names)]
    return Cell(name, entry, conf, mix, params, e2e, per_layer)


def reader(metric: str):
    """The module that reads ``metric`` (its name up to the first dot)."""
    base = metric.split(".", 1)[0]
    path = BENCH / "metrics" / f"{base}.py"
    spec = importlib.util.spec_from_file_location(f"metrics.{base}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(cell: Cell):
    return importlib.import_module(f"refs.{cell.conf['reference']}")


def peaks(kind: str) -> Dict[str, Any]:
    table = read_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise RuntimeError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def percentile(values, q: float) -> float:
    """Linear interpolation between order statistics (numpy's default)."""
    v = np.sort(np.asarray(values, float))
    if v.size == 0:
        raise ValueError("percentile of no values")
    return float(np.percentile(v, q))


# ---------------------------------------------------------------------------
# what a metric reader sees
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Run:
    cell: Cell
    seconds: float
    window: Tuple[float, float]         # engine seconds of the measured window
    end: float                          # engine seconds when the run ended
    requests: List[Any]                 # every request sent (program objects)
    withdrawn: set                      # rids taken back unserved at the close
    emits: Dict[int, List[Tuple[int, float]]]  # rid -> [(step, t_emit)]
    setup_s: float
    prompt_len: int
    cache_len: int
    peak: Dict[str, Any]
    ref: Any
    traced: Optional[List[Tuple[float, float]]] = None  # slices traced
    trace: Optional[Dict[str, Any]] = None        # xplane.merge of slices
    obs: Optional[Dict[str, Any]] = None          # repro.obs, whole window

    @property
    def mc(self) -> Dict[str, Any]:
        return self.cell.mc

    def due(self, lo: float, hi: float) -> List[Any]:
        return [r for r in self.requests if lo <= r.arrival_s < hi
                and r.rid not in self.withdrawn]

    def token_gaps(self) -> List[float]:
        """Gaps between consecutive emitted tokens of one request, every
        gap that closes inside the window (host clock, seconds)."""
        lo, hi = self.window
        return [t1 - t0 for toks in self.emits.values()
                for (s0, t0), (s1, t1) in zip(toks, toks[1:])
                if s1 == s0 + 1 and lo <= t1 < hi]

    def emitted(self, lo: float, hi: float, first: Optional[bool] = None):
        """(rid, step, t) of tokens emitted in [lo, hi); ``first`` keeps
        only first tokens (True) or only later ones (False)."""
        out = []
        for rid, toks in self.emits.items():
            for step, t in toks:
                if lo <= t < hi and (first is None or (step == 0) == first):
                    out.append((rid, step, t))
        return out


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------
def device_info(chips: int) -> Dict[str, Any]:
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise NoChip(f"no accelerator: JAX's default platform is "
                     f"{d.platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def model_config(cell: Cell):
    from repro.models.config import ModelConfig
    mc = dict(cell.mc)
    mc["pattern"] = tuple(tuple(layer) for layer in mc["pattern"])
    return ModelConfig(name=cell.conf["name"], source=cell.conf["source"],
                       **mc)


def _adapter_class(annotate: bool):
    """``LMAdapter`` with the benchmark's prompts, and (traced runs) its
    protocol wrapped in profiler annotations, so that device gaps can be
    named by what the host was doing."""
    import contextlib
    import jax
    from repro.serving.lm import LMAdapter

    def span(name):
        return (jax.profiler.TraceAnnotation(name) if annotate
                else contextlib.nullcontext())

    class BenchAdapter(LMAdapter):
        def __init__(self, *a, rows, **k):
            self._rows = rows
            super().__init__(*a, **k)

        def prompt(self, req):
            return {"tokens": self._rows[int(req.prompt)]}

        def prefill(self, req):
            with span("bench.prefill"):
                return super().prefill(req)

        def decode(self, req, state, step):
            with span("bench.decode"):
                return super().decode(req, state, step)

        def detok(self, req, step, tok):
            with span("bench.detok"):
                return super().detok(req, step, tok)

    return BenchAdapter


class _CompileWatch:
    """Times of JAX compilations, to count any inside the window."""

    def __init__(self) -> None:
        import jax
        self.times: List[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_: Any) -> None:
        if event.endswith("backend_compile_duration"):
            self.times.append(time.monotonic())


@dataclasses.dataclass
class Program:
    """The system under test, built over the benchmark's weights and
    prompts: the program's ``LMAdapter``, compiled and warmed up."""

    adapter: Any
    wkey: Any
    prompts: np.ndarray              # (n, prompt_len) host copy of the prompts


def build(cell: Cell, *, seed: int, n_prompts: int, max_output: int,
          annotate: bool) -> Program:
    import jax
    from repro.launch import mesh as meshlib
    from repro.runtime import steps
    from repro.runtime.sharding import ShardingPolicy
    from refs import common

    ref = reference(cell)
    cfg = model_config(cell)
    P = int(cell.mix["prompt_len"])
    wkey = common.seed_key(seed, 1)
    params = jax.jit(lambda k: ref.init(cell.mc, k))(wkey)
    want = steps.abstract_params(cfg)
    if jax.tree.structure(want) != jax.tree.structure(params) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
            zip(jax.tree.leaves(want), jax.tree.leaves(params))):
        raise RuntimeError("the benchmark's weights do not match the "
                           "program's parameter tree")
    pool = jax.jit(lambda k: jax.random.randint(
        k, (n_prompts, 1, P), 0, cell.mc["vocab"], jax.numpy.int32))(
            common.seed_key(seed, 2))
    mesh = meshlib.local_mesh(model=1)
    policy = ShardingPolicy(fsdp=False, tp=False, sp=False, remat=None)
    adapter = _adapter_class(annotate)(cfg, mesh, policy, params,
                                       rows=list(pool), prompt_len=P,
                                       gen_len=max_output)
    adapter.warmup()
    return Program(adapter, wkey, np.asarray(pool)[:, 0])


@dataclasses.dataclass
class Served:
    """What one drive of the engine left: requests, emissions, clocks."""

    requests: List[Any]
    withdrawn: set
    emits: Dict[int, List[Tuple[int, float]]]
    t0: float                        # the engine's start, monotonic
    end: float                       # engine seconds when the drive ended
    finished: bool
    failure: List[BaseException]
    traced: Dict[str, Any]
    obs: Optional[Dict[str, Any]]


def serve(program: Program, plan: "traffic.Plan", *,
          trace: bool) -> Served:
    """Drive ``ServingEngine.run`` with the engine's defaults over the
    plan's requests (open loop) or clients (closed loop)."""
    from repro.serving import Request, RequestState, ServingEngine

    W0, W1 = plan.window
    requests: List[Any] = []
    withdrawn: set = set()
    if plan.loop == "open":
        for i, (t, g) in enumerate(zip(plan.arrivals, plan.outputs)):
            requests.append(Request(rid=i, prompt=i, gen_len=g, arrival_s=t))
        initial = list(requests)
        on_round = None
    else:
        queues = [list(c) for c in plan.clients]
        current: Dict[int, Any] = {}
        ids = iter(range(plan.n_prompts))

        def send(client: int, t: float):
            rid = next(ids)
            req = Request(rid=rid, prompt=rid, gen_len=queues[client].pop(0),
                          arrival_s=t)
            requests.append(req)
            current[client] = req
            return req

        initial = [send(c, 0.0) for c in range(len(queues))]

        def on_round(engine, _rounds):
            now = time.monotonic() - engine._t0
            if now >= W1:
                while engine.queue:
                    withdrawn.add(engine.queue.pop().rid)
                return
            for c, req in list(current.items()):
                if req.state is RequestState.DONE and queues[c]:
                    engine.queue.push(send(c, now))

    engine = ServingEngine(program.adapter, on_round=on_round)
    traced: Dict[str, Any] = {}
    tracer_thread = None
    if trace:
        from repro.obs import registry, trace as obs_trace
        obs_trace.set_tracer(obs_trace.Tracer(capacity=OBS_CAPACITY))

        def prefilled(since: float) -> bool:
            return any(r.admitted_at is not None and r.admitted_at >= since
                       and r.tokens for r in list(requests))

        tracer_thread = threading.Thread(
            target=_profile, daemon=True,
            args=(engine, (W0, W1), plan.arrivals, prefilled, traced,
                  registry.REGISTRY))
        tracer_thread.start()

    done = threading.Event()
    failure: List[BaseException] = []

    def drive():
        try:
            engine.run(initial)
        except Exception as exc:   # reported as a failed run
            failure.append(exc)
        finally:
            done.set()

    threading.Thread(target=drive, daemon=True).start()
    finished = done.wait(timeout=W1 + DRAIN_S)
    end = time.monotonic() - engine._t0
    if tracer_thread is not None:
        tracer_thread.join(timeout=TRACE_STOP_S)
        if tracer_thread.is_alive():
            traced.setdefault("error", "the profiler did not stop in time")
    emits: Dict[int, List[Tuple[int, float]]] = {}
    for rec in list(engine.metrics.records):
        emits.setdefault(rec.rid, []).append((rec.step, rec.t_emit))
    for toks in emits.values():
        toks.sort()
    obs = None
    if trace:
        from repro.obs import trace as obs_trace
        obs = _obs_readings(obs_trace.get_tracer(), engine, (W0, W1),
                            traced.get("dispatch"))
        obs_trace.set_tracer(None)
    return Served(requests, withdrawn, emits, engine._t0, end, finished,
                  failure, traced, obs)


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             t_process: float, log=print) -> Dict[str, Any]:
    import jax
    from repro.launch.cache import enable_compile_cache

    device = device_info(int(cell.entry["chips"]))
    peak = peaks(device["kind"])
    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"[bench] {cell.name} seed={seed} seconds={seconds} trace={int(trace)}"
        f" device={device} cache={cache_dir}")
    watch = _CompileWatch()
    plan = traffic.plan(cell.mix, cell.params, seed, seconds)
    program = build(cell, seed=seed, n_prompts=plan.n_prompts,
                    max_output=plan.max_output, annotate=trace)
    served = serve(program, plan, trace=trace)
    memory_peak = (jax.devices()[0].memory_stats() or {}).get(
        "peak_bytes_in_use", 0)
    W0, W1 = plan.window
    setup_s = served.t0 + W0 - t_process
    compiles = sum(served.t0 + W0 <= t < served.t0 + W1 for t in watch.times)

    run = Run(cell=cell, seconds=seconds, window=(W0, W1), end=served.end,
              requests=served.requests, withdrawn=served.withdrawn,
              emits=served.emits, setup_s=setup_s, prompt_len=plan.prompt_len,
              cache_len=plan.prompt_len + plan.max_output, peak=peak,
              ref=reference(cell), obs=served.obs)
    if trace:
        tr = served.traced
        if "error" in tr or not tr.get("slices"):
            raise RuntimeError(f"tracing failed: {tr}")
        run.traced = [sl["window"] for sl in tr["slices"]]
        t = time.monotonic()
        run.trace = xplane.merge([xplane.reduce_file(sl["file"])
                                  for sl in tr["slices"]])
        tr["tmp"].cleanup()
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        log(f"[bench] traced {len(run.traced)} slices at "
            f"{[(round(a, 2), round(b - a, 2)) for a, b in run.traced]} "
            f"(start, seconds); the profiler stopped "
            f"in {[round(sl['stop_s'], 1) for sl in tr['slices']]} s; "
            f"read in {time.monotonic() - t:.1f} s")
        log(f"[bench] traced {run.trace['window_s']:.3f} s: modules "
            f"{[(k, len(v)) for k, v in run.trace['modules'].items()][:8]}; "
            f"custom calls {len(run.trace['custom_calls'])}, e.g. "
            f"{[n[:160] for n, _ in run.trace['custom_calls'][:1]]}")
    device["memory_peak_bytes"] = int(memory_peak)

    metrics: Dict[str, Any] = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = reader(m["name"]).read(run, m)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    # -- correctness, with the program's state freed ---------------------------
    requests = served.requests
    outputs = {r.rid: list(r.tokens) for r in requests}
    states = {r.rid: r.state.name for r in requests}
    gen = {r.rid: r.gen_len for r in requests}
    due = [r.rid for r in run.due(W0, W1)]
    if plan.loop == "closed":
        # a closed loop's requests are due when their client sends them;
        # count every request in service during the window
        due = [r.rid for r in requests if r.rid not in run.withdrawn
               and r.admitted_at is not None and r.admitted_at < W1
               and (r.finished_at is None or r.finished_at >= W0)]
    wkey, prompts = program.wkey, program.prompts
    engine_ok = served.finished and not served.failure
    if not engine_ok:
        log(f"[bench] the engine did not finish: finished={served.finished}"
            f" errors={[repr(e)[:300] for e in served.failure]}")
    del program, served, requests
    run.requests = []
    gc.collect()
    log(f"[bench] window closed; setup_s={setup_s:.3f} "
        f"compiles_in_window={compiles} requests={len(states)} "
        f"withdrawn={len(run.withdrawn)}")
    checks = check.run_checks(
        cell=cell, ref=run.ref, wkey=wkey, prompts=prompts, served=outputs,
        states=states, gen=gen, withdrawn=run.withdrawn, seed=seed, log=log,
        engine_failed=not engine_ok)
    result = {
        "correct": all(c["value"] <= c["limit"] for c in checks.values()),
        "attempted": len(due),
        "failed": sum(1 for rid in due if states[rid] != "DONE"),
        "metrics": metrics,
        "device": device,
    }
    if trace:
        result["breakdown"] = _breakdown(run.trace)
    result["checks"] = checks
    return result, engine_ok


def slice_start(earliest: float, arrivals) -> float:
    """Where a slice that may start at ``earliest`` (engine seconds) starts:
    there in a closed loop, and in an open loop TRACE_LEAD_S before the
    first arrival that leaves room for it (``inf`` where none does)."""
    if not arrivals:
        return earliest
    return min((t - TRACE_LEAD_S for t in arrivals
                if t - TRACE_LEAD_S >= earliest), default=math.inf)


def _profile(engine, window: Tuple[float, float], arrivals,
             prefilled, out: Dict[str, Any], registry) -> None:
    """Trace TRACE_SLICES slices of the window (engine seconds) with the
    device profiler, each into a directory of its own and marked by an
    annotation, and each held open past TRACE_SLICE_S until
    ``prefilled(start)`` says that a request admitted since its start has
    emitted its first token; reset the runtime's counters at the window's
    start and read them at its end.  A slice that the last one's stop has
    overrun starts late, and is left out if it would end past the
    window."""
    import jax
    try:
        while engine._t0 == 0.0:
            time.sleep(0.001)
        t0 = engine._t0
        tmp = tempfile.TemporaryDirectory(prefix="bench-trace-")
        out["tmp"] = tmp
        out["slices"] = []
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        W0, W1 = window
        part = (W1 - W0) / TRACE_SLICES
        time.sleep(max(0.0, t0 + W0 - time.monotonic()))
        registry.reset()
        for i in range(TRACE_SLICES):
            middle = W0 + (i + 0.5) * part - TRACE_SLICE_S / 2
            lo = slice_start(max(middle, time.monotonic() - t0 + 0.5),
                             arrivals)
            if lo + TRACE_SLICE_S > W1:
                break
            log_dir = os.path.join(tmp.name, str(i))
            time.sleep(max(0.0, t0 + lo - 0.5 - time.monotonic()))
            jax.profiler.start_trace(log_dir, profiler_options=opts)
            time.sleep(max(0.0, t0 + lo - time.monotonic()))
            a = time.monotonic()
            last = min(a + TRACE_SLICE_MAX_S, t0 + W1)
            with jax.profiler.TraceAnnotation(xplane.WINDOW):
                time.sleep(max(0.0, a + TRACE_SLICE_S - time.monotonic()))
                while not prefilled(a - t0) and time.monotonic() < last:
                    time.sleep(0.01)
            b = time.monotonic()
            jax.profiler.stop_trace()
            out["slices"].append({"window": (a - t0, b - t0),
                                  "stop_s": time.monotonic() - b,
                                  "file": xplane.find(log_dir)})
        time.sleep(max(0.0, t0 + W1 - time.monotonic()))
        out["dispatch"] = registry.histogram(
            "continuation.dispatch_latency_s").summary()
    except Exception as exc:   # surfaced by run_cell
        out["error"] = repr(exc)


def _obs_readings(tracer, engine, window, dispatch) -> Dict[str, Any]:
    """Host seconds that runtime tasks ran within the window (from the
    ``task``/``run`` spans), and the continuation dispatch latency."""
    lo, hi = window
    lo_us = (engine._t0 + lo - tracer.epoch) * 1e6
    hi_us = (engine._t0 + hi - tracer.epoch) * 1e6
    busy = 0.0
    events = tracer.events()
    for ev in events:
        if ev.get("ph") == "X" and ev["cat"] == "task" and ev["name"] == "run":
            busy += max(0.0, min(ev["ts"] + ev["dur"], hi_us)
                        - max(ev["ts"], lo_us))
    # a ring that filled up has dropped its oldest spans
    full = any(len(ring) == tracer.capacity for ring in tracer._rings)
    return {"task_run_s": None if full else busy * 1e-6,
            "dispatch": dispatch}


def _breakdown(tr: Dict[str, Any]) -> Dict[str, Any]:
    ops = [[name, s] for name, s in tr["top_modules"][:9]]
    kernel = sum(s for _, s in tr["custom_calls"])
    if kernel:
        ops.append(["custom-call (Pallas kernels)", kernel])
    return {"device_ops": ops,
            "idle_gaps": [[f"host:{n}", s] for n, s in tr["idle_gaps"][:10]]}


def main(argv=None, t_process: Optional[float] = None) -> int:
    import argparse
    t_process = time.monotonic() if t_process is None else t_process
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = p.parse_args(argv)
    cell = load_cell(args.workload)

    def log(msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)

    try:
        result, engine_ok = run_cell(cell, seed=args.seed,
                                     seconds=args.seconds,
                                     trace=bool(args.trace),
                                     t_process=t_process, log=log)
    except NoChip as exc:
        log(f"[bench] {exc}")
        return 3
    for name, c in result["checks"].items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r} "
            f"{'ok' if c['value'] <= c['limit'] else 'FAILED'}")
    sys.stdout.write(json.dumps(result) + "\n")
    sys.stdout.flush()
    sys.stderr.flush()
    if not engine_ok:
        # The engine's threads are still running a request that never
        # finished; end the process rather than wait on them.
        os._exit(0)
    return 0
