"""Serving benchmark: event-bound vs blocking-sentinel completion.

Drives :class:`repro.serving.engine.ServingEngine` over a synthetic
multi-tenant trace — Poisson arrivals, two priority tenants, mixed
generation lengths — once per completion leg, on the SAME trace and the
SAME adapter (``repro.serving.synthetic.SyntheticAdapter``: prefills
and decode rounds complete asynchronously, in order, on a device-queue
thread, host detokenisation is real GIL-releasing work).  The structural
claim under test is the paper's: the blocking-sentinel leg holds the
device chain through every device wait and the detokenisation of every
row, while the event-bound leg (``tac.iwait`` → continuation engine)
frees it at dispatch and detokenises while the next rounds run.

Hard acceptance (exits non-zero on violation):

* the two legs emit bit-identical token streams;
* event-bound tokens/s >= blocking tokens/s;
* event-bound p99 latency <= blocking p99 latency.

Writes ``BENCH_serve.json`` with gated calibration rows
``serve.event`` / ``serve.blocking`` (``measured_s`` + linear cost
features + ``overhead_class "serve:<leg>"``) and ``gate_scope:
["serve"]`` so ``tools/calibrate.py --gate`` holds this bench
accountable for exactly its own baseline rows.  Features (per leg, both
legs identical — only ``measured_s`` differs): ``rounds`` = tokens
made (one prefill or one round's row each), ``wire_bytes`` =
device-occupancy proxy (tokens × device latency in µs),
``combine_bytes`` = host detok bytes (tokens × hash rounds × 64 KiB).

CSV: name,us_per_call,derived
"""

from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys

import numpy as np

from repro.serving import (Request, ServingEngine, SyntheticAdapter,
                           token_at)

HOST_BUF_BYTES = 64 * 1024


def make_trace(n: int, *, seed: int, rate_per_s: float,
               gen_choices) -> list:
    """Poisson multi-tenant trace: two priority classes, mixed lengths."""
    rng = np.random.default_rng(seed)
    t = 0.0
    reqs = []
    for i in range(n):
        t += rng.exponential(1.0 / rate_per_s)
        reqs.append(Request(
            rid=i, prompt=100 + 17 * i,
            gen_len=int(rng.choice(gen_choices)),
            priority=int(rng.random() < 0.25),   # 25% batch tenant
            arrival_s=t))
    return reqs


def run_leg(leg: str, trace, adapter, *, slots: int,
            workers: int) -> dict:
    engine = ServingEngine(adapter, slots=slots, completion=leg,
                           num_workers=workers)
    # fresh Request objects per leg: state machines are single-use
    reqs = [Request(rid=r.rid, prompt=r.prompt, gen_len=r.gen_len,
                    priority=r.priority, arrival_s=r.arrival_s)
            for r in trace]
    report = engine.run(reqs)
    for r in reqs:
        want = [token_at(r.prompt, s) for s in range(r.gen_len)]
        if report.outputs[r.rid] != want:
            raise SystemExit(
                f"serve_bench: token parity violation on the {leg} leg, "
                f"request {r.rid}: got {report.outputs[r.rid]}, "
                f"want {want}")
    return report


def bench(*, smoke: bool = False, seed: int = 0,
          json_path: str = "BENCH_serve.json",
          print_fn=print) -> dict:
    n, gen_choices = (24, (4, 8, 12)) if smoke else (64, (8, 16, 24))
    slots, workers = 16, 4
    dev_ms, host_rounds = 30.0, 8
    trace = make_trace(n, seed=seed, rate_per_s=400.0,
                       gen_choices=gen_choices)
    total_steps = sum(r.gen_len for r in trace)
    features = {
        "rounds": float(total_steps),
        "wire_bytes": float(total_steps) * dev_ms * 1e3,
        "combine_bytes": float(total_steps) * host_rounds
                         * HOST_BUF_BYTES,
    }

    adapter = SyntheticAdapter(dev_ms=dev_ms, host_rounds=host_rounds,
                               slots=slots)
    adapter.warmup()
    report = {"requests": n, "slots": slots, "workers": workers,
              "dev_ms": dev_ms, "host_rounds": host_rounds,
              "serve": {}}
    legs = {}
    try:
        for leg in ("event", "blocking"):
            # untimed warm pass: thread pools, runtime, code paths
            run_leg(leg, make_trace(4, seed=seed + 1, rate_per_s=1e6,
                                    gen_choices=(2,)),
                    adapter, slots=slots, workers=workers)
            gc.collect()    # no full collection inside one timed leg
            rep = run_leg(leg, trace, adapter, slots=slots,
                          workers=workers)
            legs[leg] = rep
            report["serve"][leg] = {
                "measured_s": rep.wall_s,
                "features": features,
                "overhead_class": f"serve:{leg}",
                "tokens": rep.tokens,
                "tokens_per_s": rep.tokens_per_s,
                "p50_ms": rep.p50_ms,
                "p99_ms": rep.p99_ms,
            }
            print_fn(f"serve_{leg},{rep.wall_s / max(rep.tokens, 1) * 1e6:.1f},"
                     f"tok_s={rep.tokens_per_s:.0f};p50={rep.p50_ms:.1f};"
                     f"p99={rep.p99_ms:.1f}")
    finally:
        adapter.close()

    ev, bl = legs["event"], legs["blocking"]
    report["speedup_tokens_per_s"] = ev.tokens_per_s / bl.tokens_per_s
    report["p99_ratio"] = ev.p99_ms / bl.p99_ms
    report["gate_scope"] = ["serve"]
    pathlib.Path(json_path).write_text(json.dumps(report, indent=2))
    print_fn(f"serve_report_json,0.0,{json_path}")
    print_fn(f"serve_speedup,{report['speedup_tokens_per_s']:.2f},"
             f"p99_ratio={report['p99_ratio']:.2f}")

    if ev.tokens_per_s < bl.tokens_per_s:
        raise SystemExit(
            f"serve_bench: event-bound leg slower than blocking sentinel "
            f"({ev.tokens_per_s:.0f} vs {bl.tokens_per_s:.0f} tok/s) — "
            f"the task-aware completion path regressed")
    if ev.p99_ms > bl.p99_ms:
        raise SystemExit(
            f"serve_bench: event-bound p99 above blocking sentinel "
            f"({ev.p99_ms:.1f} vs {bl.p99_ms:.1f} ms) — the task-aware "
            f"completion path regressed")
    return report


def run_traced(trace_path: str, *, seed: int = 0, print_fn=print) -> float:
    """``--trace`` leg: one event-bound serve run under the tracer.

    Exports the serving micro-step timeline (device_step / detok spans,
    token instants, handle in-flight windows) as Perfetto JSON; exits
    non-zero if the document violates ``repro.obs.SPAN_SCHEMA``.
    """
    from repro import obs

    trace = make_trace(16, seed=seed, rate_per_s=400.0,
                       gen_choices=(4, 8))
    adapter = SyntheticAdapter(dev_ms=10.0, host_rounds=4, slots=16)
    adapter.warmup()
    try:
        with obs.tracing(capacity=1 << 18) as tr:
            rep = run_leg("event", trace, adapter, slots=16, workers=4)
            events = tr.events()
    finally:
        adapter.close()
    counts = obs.summarize(events)["counts"]
    doc = obs.export_trace(trace_path, events=events, extra={
        "benchmark": "serve_bench", "completion": "event",
        "tokens": rep.tokens, "tokens_per_s": rep.tokens_per_s,
        "p99_ms": rep.p99_ms,
    })
    obs.assert_valid_trace(doc)
    if not counts.get("serving/device_step[X]"):
        raise SystemExit("serve_bench --trace: no device_step spans "
                         "recorded")
    print_fn(f"serve_trace_events,{len(events)},file={trace_path};"
             f"tokens={rep.tokens}")
    return rep.tokens_per_s


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--smoke", action="store_true")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--json", default="BENCH_serve.json")
    p.add_argument("--trace", metavar="PATH", default=None,
                   help="run one traced event-bound leg and write Perfetto "
                        "JSON here (skips the comparison bench)")
    args = p.parse_args(argv)
    if args.trace:
        run_traced(args.trace, seed=args.seed)
        return 0
    bench(smoke=args.smoke, seed=args.seed, json_path=args.json)
    return 0


if __name__ == "__main__":
    sys.exit(main())
