"""Serving CLI: continuous batching on the task runtime.

Thin front-end over :class:`repro.serving.engine.ServingEngine` with the
real model path (:class:`repro.serving.lm.LMAdapter`): ``--batch``
requests are admitted through the engine's queue and decoded greedily,
each prefill, each decode round over the ``--slots`` rows and each host
detokenisation a runtime task, with device completion bound through the
AsyncHandle protocol (``--completion event``) or synchronised in-task
(``--completion blocking``, the sentinel baseline).

  PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b \
      --batch 4 --prompt-len 32 --gen 32
"""

from __future__ import annotations

import argparse
import functools
import sys
from typing import Any, Sequence

import jax

from .. import configs
from ..models import model
from ..runtime.sharding import ShardingPolicy
from ..serving import Request, ServeReport, ServingEngine
from ..serving.lm import LMAdapter
from . import mesh as meshlib
from .cache import enable_compile_cache


def model_config(arch: str, scale: str) -> Any:
    """The registry config at ``scale`` ("full": the published widths)."""
    return configs.smoke(arch) if scale == "smoke" else configs.get(arch)


def build_adapter(cfg: Any, *, prompt_len: int, gen: int, slots: int,
                  seed: int = 0) -> LMAdapter:
    """Random weights from ``seed`` on a one-device mesh, behind a
    compiled and warmed-up :class:`LMAdapter` of ``slots`` rows."""
    mesh = meshlib.local_mesh(model=1)
    policy = ShardingPolicy(fsdp=False, tp=False, sp=False, remat=None)
    params = jax.jit(functools.partial(model.init, cfg))(
        jax.random.PRNGKey(seed))
    adapter = LMAdapter(cfg, mesh, policy, params,
                        prompt_len=prompt_len, gen_len=gen, slots=slots)
    adapter.warmup()
    return adapter


def make_requests(n: int, *, gen: int, seed: int = 0) -> Sequence[Request]:
    return [Request(rid=i, prompt=seed * 1000 + i, gen_len=gen)
            for i in range(n)]


def serve(adapter: LMAdapter, requests: Sequence[Request], *, slots: int,
          completion: str, workers: int) -> ServeReport:
    """Serve ``requests`` through the task-runtime engine to completion."""
    engine = ServingEngine(adapter, slots=slots, completion=completion,
                           num_workers=workers)
    return engine.run(requests)


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="granite-3-2b")
    p.add_argument("--scale", default="smoke", choices=["smoke", "full"])
    p.add_argument("--batch", type=int, default=4,
                   help="number of requests to serve")
    p.add_argument("--prompt-len", type=int, default=32)
    p.add_argument("--gen", type=int, default=32)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--slots", type=int, default=4,
                   help="max in-flight requests (continuous batching)")
    p.add_argument("--completion", default="event",
                   choices=["event", "blocking"])
    p.add_argument("--workers", type=int, default=4)
    args = p.parse_args(argv)

    enable_compile_cache()
    cfg = model_config(args.arch, args.scale)
    if not cfg.causal:
        print(f"[serve] {cfg.name} is encoder-only: no decode step")
        return 0
    adapter = build_adapter(cfg, prompt_len=args.prompt_len, gen=args.gen,
                            slots=args.slots, seed=args.seed)
    requests = make_requests(args.batch, gen=args.gen, seed=args.seed)
    report = serve(adapter, requests, slots=args.slots,
                   completion=args.completion, workers=args.workers)

    print(f"[serve] arch={cfg.name} requests={args.batch} "
          f"prompt={args.prompt_len} gen={args.gen} slots={args.slots} "
          f"completion={args.completion}")
    print(f"[serve] {report.summary()}")
    sample = report.outputs[0][:16]
    print(f"[serve] sample continuation (req 0): {sample}")
    if any(len(report.outputs[r.rid]) != args.gen for r in requests):
        print("[serve] FAILED: a request got fewer than --gen tokens")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
