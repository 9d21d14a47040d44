#!/usr/bin/env python3
"""Smoke run of the main paths on a TPU — a check that they run and are
right, not a benchmark.

One process, phases in order, stopping at the first failure with a
non-zero exit:

a. device check: ``jax.devices()[0].platform`` must be ``"tpu"``; there is
   no CPU fallback.
b. serve: granite-3-2b at its published widths (random weights from
   ``--seed``) through the same calls as ``python -m repro.launch.serve``:
   4 requests, 128-token prompts, 32 generated tokens, 4 slots, once with
   ``completion="event"`` and once with ``"blocking"``.  Both legs must
   give every request all its tokens, and the same tokens.  The Pallas
   prefill must match the jnp reference (``impl="ref"``) within
   ``LOGIT_RTOL`` and agree on the greedy first token, and its compiled
   program must hold the kernel (``tpu_custom_call``).
c. stencil: the task runtime drives the fused Gauss–Seidel kernel on
   512×512 fp32 blocks (``interop-nonblk``) and must match the float64
   ``pure`` run within 1e-4.

``--chips 4`` runs only the Level-B collectives on a 4-device mesh, each
compared shard by shard with ``lax.psum`` (or fused grad sync, or a NumPy
shift), and reports ``"count": 4``.

The last line of stdout is ``{"ok": true, "device": {...}}``.

  python chip_smoke.py            # one chip
  python chip_smoke.py --chips 4  # four chips: collectives only
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from repro.launch.cache import enable_compile_cache  # noqa: E402

# Pallas vs jnp-reference prefill logits: bf16 keeps 8 significant bits
# (spacing 2^-7 relative), and the two paths round differently at every
# one of granite's 40 layers.  Bound: max |Δ| ≤ 0.05 · max |ref|, about
# six bf16 spacings at the largest logit.
LOGIT_RTOL = 0.05
# Fused stencil (fp32) vs the float64 pure run: the bound the
# Gauss–Seidel benchmark's fused leg uses.
STENCIL_TOL = 1e-4
# Collectives, relative to max |psum| on each device: exact-op fp32
# reorderings, then the bf16 and int8 wires (as in tests/test_lowering.py).
FP32_RTOL, BF16_RTOL, INT8_RTOL = 1e-5, 2e-2, 5e-2


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"[smoke] FAILED: {what}")


def device_check(count: int) -> dict:
    """Phase a: the default backend is a TPU with at least ``count`` chips."""
    import jax
    devs = jax.devices()
    d = devs[0]
    log(f"jax {jax.__version__}; platform={d.platform} "
        f"kind={d.device_kind!r} count={len(devs)}")
    check(d.platform == "tpu", f"no TPU: default platform is {d.platform!r}")
    check(len(devs) >= count, f"need {count} chips, found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def serve_phase(*, scale: str = "full", impl: str | None = None,
                requests: int = 4, prompt_len: int = 128, gen: int = 32,
                slots: int = 4, workers: int = 4, seed: int = 0) -> dict:
    """Phase b: task-aware serving of granite-3-2b, both completion legs."""
    import jax
    import numpy as np
    from repro.launch import serve
    from repro.serving.lm import LMAdapter

    cfg = serve.model_config("granite-3-2b", scale)
    if impl is not None:
        cfg = cfg.scaled(kernel_impl=impl)
    log(f"serve: {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
        f"heads={cfg.n_heads}/{cfg.n_kv_heads} head_dim={cfg.head_dim} "
        f"d_ff={cfg.d_ff} vocab={cfg.vocab} dtype={cfg.dtype}")
    t0 = time.monotonic()
    adapter = serve.build_adapter(cfg, prompt_len=prompt_len, gen=gen,
                                  slots=slots, seed=seed)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(adapter.params))
    setup_s = time.monotonic() - t0
    log(f"serve: {n_params / 1e9:.3f} B params; init + prefill/decode "
        f"compile + warm-up {setup_s:.1f} s")

    outputs = {}
    for completion in ("event", "blocking"):
        reqs = serve.make_requests(requests, gen=gen, seed=seed)
        report = serve.serve(adapter, reqs, slots=slots,
                             completion=completion, workers=workers)
        log(f"serve (smoke, not a benchmark): {report.summary()}")
        for r in reqs:
            got = len(report.outputs[r.rid])
            check(got == gen, f"{completion}: request {r.rid} got {got} "
                              f"of {gen} tokens")
        outputs[completion] = report.outputs
    check(outputs["event"] == outputs["blocking"],
          "event and blocking legs gave different tokens")

    probe = serve.make_requests(1, gen=gen, seed=seed)[0]
    ref = LMAdapter(cfg.scaled(kernel_impl="ref"), adapter.mesh,
                    adapter.policy, adapter.params, prompt_len=prompt_len,
                    gen_len=gen, slots=1)
    got = np.asarray(adapter.prefill_logits(probe), np.float32)
    want = np.asarray(ref.prefill_logits(probe), np.float32)
    check(np.all(np.isfinite(got)), "non-finite Pallas prefill logits")
    err = float(np.max(np.abs(got - want)))
    bound = LOGIT_RTOL * float(np.max(np.abs(want)))
    log(f"serve: prefill logits max|pallas - ref| = {err:.6g} "
        f"(bound {bound:.6g}); first tokens "
        f"{int(got.argmax())} / {int(want.argmax())}")
    check(err <= bound, f"prefill logits differ from ref by {err}")
    check(int(got.argmax()) == int(want.argmax()),
          "greedy first token differs from ref")
    if cfg.kernel_impl in (None, "pallas"):
        hlo = adapter.compiled_prefill(probe).as_text()
        check("tpu_custom_call" in hlo,
              "compiled prefill holds no Pallas kernel (tpu_custom_call)")
        log("serve: compiled prefill contains tpu_custom_call")
    return {"setup_s": setup_s, "params": int(n_params),
            "tokens": outputs["event"], "logit_err": err}


def stencil_phase(*, impl: str = "pallas", bs: int = 512) -> dict:
    """Phase c: task runtime driving the fused Gauss–Seidel kernel."""
    import numpy as np
    from benchmarks import gauss_seidel as gs

    t0 = time.monotonic()
    out, st = gs.run_real("interop-nonblk", block_impl=impl, bs=bs)
    wall = time.monotonic() - t0
    ref, ref_st = gs.run_real("pure", bs=bs)
    err = float(np.max(np.abs(out - ref)))
    log(f"stencil: {out.shape} grid, {bs}x{bs} fp32 blocks, impl={impl}: "
        f"max|fused - pure| = {err:.3g}; wall {wall:.2f} s "
        f"(smoke, not a benchmark)")
    check(err < STENCIL_TOL, f"stencil differs from pure by {err}")
    for it, val in ref_st["residuals"].items():
        got = st["residuals"][it]
        check(abs(got - val) <= STENCIL_TOL * max(1.0, val),
              f"residual at iteration {it}: {got} vs {val}")
    return {"err": err}


def collectives_phase(*, impl: str = "pallas", elems: int = 16 << 20,
                      grad_layers: str = "full") -> dict:
    """``--chips 4``: Level-B collectives on four devices, each checked
    on every device against its reference.

    ``elems`` fp32 elements per device (default 64 MiB); the grad-sync
    pytree has the layer leaves of a 4-layer granite-3-2b (``"full"``
    widths, or ``"smoke"`` widths for a CPU run).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import lowering, overlap
    from repro.launch import serve
    from repro.launch.mesh import make_mesh
    from repro.models import model

    n = 4
    flat = make_mesh((n,), ("data",))
    pods = make_mesh((2, 2), ("pod", "data"))
    results = {}

    def spmd(fn, mesh, in_specs, out_specs):
        return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                     out_specs=out_specs, check_vma=False))

    def random(mesh, spec, shape, seed):
        return jax.jit(lambda k: jax.random.normal(k, shape, jnp.float32),
                       out_shardings=NamedSharding(mesh, spec))(
            jax.random.PRNGKey(seed))

    def allreduce_case(name, mesh, axes, tol, **kw):
        spec = P(axes)
        x = random(mesh, spec, (n, elems), 1)

        def body(xl):
            v = xl.reshape(-1)
            got = lowering.allreduce(v, axes, **kw)
            want = lax.psum(v, axes)
            scale = jnp.max(jnp.abs(want))
            return (jnp.max(jnp.abs(got - want)) / scale)[None]

        rel = np.asarray(spmd(body, mesh, spec, spec)(x))
        log(f"collectives: {name}: max rel err per device "
            f"{[float(f'{r:.3g}') for r in rel]} (bound {tol})")
        check(rel.shape == (n,) and bool(np.all(rel < tol)),
              f"{name} differs from lax.psum: {rel}")
        results[name] = rel.tolist()

    allreduce_case("ring", flat, "data", FP32_RTOL, algorithm="ring")
    allreduce_case("ring/segments=4", flat, "data", FP32_RTOL,
                   algorithm="ring", segments=4)
    allreduce_case("ring/stage=pallas/wire=bf16", flat, "data", BF16_RTOL,
                   algorithm="ring", stage_impl=impl, stage_wire="bf16")
    allreduce_case("ring/stage=pallas/wire=int8", flat, "data", INT8_RTOL,
                   algorithm="ring", stage_impl=impl, stage_wire="int8")
    allreduce_case("doubling", flat, "data", FP32_RTOL,
                   algorithm="doubling")
    allreduce_case("hierarchical(pod=2,data=2)", pods, ("pod", "data"),
                   FP32_RTOL, algorithm="hierarchical")

    # Bucketed vs fused gradient sync over granite-3-2b layer leaves.
    cfg = serve.model_config("granite-3-2b", grad_layers).scaled(n_layers=4)
    shapes = jax.eval_shape(lambda k: model.init(cfg, k),
                            jax.random.PRNGKey(0))["layers"]
    leaves, treedef = jax.tree_util.tree_flatten(shapes)
    n_elems = sum(int(np.prod(s.shape)) for s in leaves)

    def grads_body(key):
        key = jax.random.fold_in(key, lax.axis_index("data"))
        g = treedef.unflatten([
            jax.random.normal(jax.random.fold_in(key, i), s.shape, s.dtype)
            for i, s in enumerate(leaves)])
        fused = overlap.sync_grads(g, axes=("data",), mode="fused")
        bucketed = overlap.sync_grads(g, axes=("data",), mode="bucketed")
        mean = jax.tree_util.tree_map(
            lambda x: (lax.psum(x.astype(jnp.float32), "data") / n
                       ).astype(x.dtype), g)

        def maxdiff(a, b):
            return jnp.max(jnp.stack([
                jnp.max(jnp.abs(x.astype(jnp.float32) - y.astype(jnp.float32)))
                for x, y in zip(jax.tree_util.tree_leaves(a),
                                jax.tree_util.tree_leaves(b))]))
        scale = jnp.max(jnp.stack([jnp.max(jnp.abs(x.astype(jnp.float32)))
                                   for x in jax.tree_util.tree_leaves(mean)]))
        return jnp.stack([maxdiff(bucketed, fused), maxdiff(fused, mean),
                          scale])[None]

    out = np.asarray(spmd(grads_body, flat, P(), P("data"))(
        jax.random.PRNGKey(2)))
    # fp32 sums cast back to the leaf dtype: one rounding step of it apart.
    tol = float(jnp.finfo(leaves[0].dtype).eps)
    log(f"collectives: sync_grads bucketed vs fused over {len(leaves)} "
        f"leaves ({n_elems / 1e6:.1f} M {leaves[0].dtype} elements): "
        f"max|Δ| per device {out[:, 0].tolist()}, fused vs psum mean "
        f"{out[:, 1].tolist()}, max|mean| {out[:, 2].tolist()}")
    check(out.shape == (n, 3), f"grad sync output shape {out.shape}")
    check(bool(np.all(out[:, 0] <= tol * out[:, 2])),
          "bucketed grad sync differs from fused")
    check(bool(np.all(out[:, 1] <= tol * out[:, 2])),
          "fused grad sync differs from the psum mean")
    results["sync_grads"] = out.tolist()

    # Halo rows vs a NumPy shift of the row-sharded grid.
    cols = 4096 if elems % 4096 == 0 else 128
    rows = elems // cols
    x = random(flat, P("data"), (n * rows, cols), 3)
    top, bottom = spmd(lambda xl: overlap.halo_exchange_rows(xl, "data"),
                       flat, P("data"), (P("data"), P("data")))(x)
    first = np.asarray(x[::rows])                 # first row of each shard
    last = np.asarray(x[rows - 1::rows])          # last row of each shard
    zero = np.zeros((1, cols), np.float32)
    want_top = np.concatenate([zero, last[:-1]])
    want_bottom = np.concatenate([first[1:], zero])
    for got, want in ((top, want_top), (bottom, want_bottom)):
        for sh in got.addressable_shards:
            i = sh.index[0].start or 0
            check(np.array_equal(np.asarray(sh.data), want[i:i + 1]),
                  f"halo rows on {sh.device} differ from the NumPy shift")
    log(f"collectives: halo_exchange_rows ({rows}x{cols} fp32 per device) "
        f"matches the NumPy shift on all {n} devices")
    results["halo"] = True
    return results


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, default=1, choices=[1, 4],
                   help="4: run only the Level-B collectives on 4 chips")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    cache_dir = pathlib.Path(enable_compile_cache())
    cached = len(list(cache_dir.glob("*"))) if cache_dir.is_dir() else 0
    log(f"compile cache {cache_dir} holds {cached} entries at start")
    t0 = time.monotonic()
    device = device_check(args.chips)
    if args.chips == 4:
        collectives_phase()
    else:
        serve_phase(seed=args.seed)
        stencil_phase()
    log(f"all phases passed in {time.monotonic() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
