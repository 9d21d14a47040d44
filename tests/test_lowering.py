"""Level-B lowering tests: one schedule IR driving in-graph execution.

jax locks the device count at first init, so these run in subprocesses
with XLA_FLAGS=--xla_force_host_platform_device_count=8 (same pattern as
tests/test_distributed.py).  Structural equivalence claims: the lowered
ppermute counts mirror the schedule's transfer structure, and the
numerics match ``lax.psum`` / the host-side reference.
"""

import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow

_ENV = dict(os.environ,
            XLA_FLAGS="--xla_force_host_platform_device_count=8",
            PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))


def _run(code: str) -> str:
    r = subprocess.run([sys.executable, "-c", code], env=_ENV,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_lowered_allreduce_matches_psum_and_schedule_structure():
    """Ring (segmented and not) and butterfly lowerings equal psum, and
    each emits exactly the schedule's per-rank transfer count of
    collective-permutes."""
    _run("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.core import lowering
from repro.core import schedule as schedule_ir

mesh = make_mesh((8,), ("data",))
x = jax.random.normal(jax.random.PRNGKey(0), (8, 1000))
want = np.asarray(jnp.sum(x, axis=0))

for alg, seg in (("ring", 1), ("ring", 4), ("doubling", 1)):
    def f(xl):
        return lowering.allreduce(xl.reshape(-1), ("data",),
                                  algorithm=alg, segments=seg)
    sf = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("data"), out_specs=P(),
                           axis_names={"data"}, check_vma=False))
    got = np.asarray(sf(x.reshape(-1)))
    assert np.max(np.abs(got - want)) < 1e-3, (alg, seg)
    txt = sf.lower(x.reshape(-1)).as_text()
    sched = schedule_ir.build("allreduce", alg, 8, segments=seg)
    n_pp = txt.count("collective_permute")
    assert n_pp == lowering.sends_per_rank(sched), (alg, seg, n_pp)
    assert txt.count("all_reduce") == 0, (alg, seg)

# native = one fused node (the sync_grads default)
def g(xl):
    return lowering.allreduce(xl.reshape(-1), ("data",))
sg = jax.jit(jax.shard_map(g, mesh=mesh, in_specs=P("data"), out_specs=P(),
                       axis_names={"data"}, check_vma=False))
txt = sg.lower(x.reshape(-1)).as_text()
assert txt.count("all_reduce") == 1 and txt.count("collective_permute") == 0
print("LOWERED-ALLREDUCE-OK")
""")


def test_lowered_allreduce_non_divisible_payload():
    """Padding path: payload not divisible by n×segments."""
    _run("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.core import lowering

mesh = make_mesh((8,), ("data",))
x = jax.random.normal(jax.random.PRNGKey(1), (8, 173))   # 173 % 8 != 0
def f(xl):
    return lowering.allreduce(xl.reshape(-1), ("data",),
                              algorithm="ring", segments=3)
sf = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("data"), out_specs=P(),
                       axis_names={"data"}, check_vma=False))
got = np.asarray(sf(x.reshape(-1)))
want = np.asarray(jnp.sum(x, axis=0))
assert got.shape == want.shape
assert np.max(np.abs(got - want)) < 1e-3
print("PAD-OK")
""")


def test_halo_exchange_rows_executes_neighbor_schedule():
    """halo_exchange_rows = the 1-D neighbourhood schedule lowered: two
    ppermutes, boundary shards get zero halos, interior shards get their
    neighbours' edge rows — and the result matches the host-side
    HaloExchange run of the SAME schedule."""
    _run("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.core import tac
from repro.core.collectives import HaloExchange
from repro.core.overlap import halo_exchange_rows

mesh = make_mesh((8,), ("data",))
g = jax.random.normal(jax.random.PRNGKey(1), (32, 5))    # 8 shards x 4 rows
def halo(xl):
    t, b = halo_exchange_rows(xl, "data", width=1)
    return jnp.concatenate([t, b], axis=0)
sh = jax.jit(jax.shard_map(halo, mesh=mesh, in_specs=P("data"),
                       out_specs=P("data"), axis_names={"data"},
                       check_vma=False))
out = np.asarray(sh(g))
assert sh.lower(g).as_text().count("collective_permute") == 2

# host-side execution of the same 1-D neighbourhood schedule
world = tac.CommWorld(8)
cart = world.cart_create((8,), periodic=False)
hx = HaloExchange(cart)
gnp = np.asarray(g)
blocks = [gnp[r * 4:(r + 1) * 4] for r in range(8)]
sends = [{d: (blocks[r][-1:] if d == (0, 1) else blocks[r][:1])
          for d, _ in hx.neighbors(r)} for r in range(8)]
got = hx.run_group(sends)
for r in range(8):
    top = got[r].get((0, -1), np.zeros((1, 5)))
    bot = got[r].get((0, 1), np.zeros((1, 5)))
    np.testing.assert_allclose(out[2 * r], top[0], atol=1e-6)
    np.testing.assert_allclose(out[2 * r + 1], bot[0], atol=1e-6)
print("HALO-PARITY-OK")
""")


def test_sync_grads_explicit_ring_matches_native():
    """sync_grads(algorithm="ring") — the bucketed schedule lowered to
    explicit rounds — agrees with the default fused-node lowering."""
    _run("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.core.overlap import sync_grads

mesh = make_mesh((8,), ("data",))
n = 3000
xs = jax.random.normal(jax.random.PRNGKey(2), (8, n))

outs = {}
for alg, seg in (("native", 1), ("ring", 1), ("ring", 2)):
    def f(xl):
        out = sync_grads({"w": xl, "b": xl[:7] * 2.0}, axes=("data",),
                         mode="bucketed", bucket_bytes=1 << 12,
                         algorithm=alg, segments=seg)
        return out["w"], out["b"]
    sf = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("data"),
                           out_specs=P(), axis_names={"data"},
                           check_vma=False))
    outs[(alg, seg)] = [np.asarray(o) for o in sf(xs.reshape(-1))]
    txt = sf.lower(xs.reshape(-1)).as_text()
    if alg == "native":
        assert txt.count("all_reduce") > 0
    else:
        assert txt.count("all_reduce") == 0
        assert txt.count("collective_permute") > 0

ref = outs[("native", 1)]
for k, v in outs.items():
    for a, b in zip(ref, v):
        np.testing.assert_allclose(a, b, atol=1e-5), k
print("SYNC-GRADS-RING-OK")
""")


def test_bucketing_uses_wire_dtype_bytes():
    """Satellite: buckets are sized by each leaf's actual bytes AS SENT
    (wire-dtype itemsize), not a hardcoded 4 B/element — under
    ``wire="leaf"`` a bf16 leaf packs twice the elements of an fp32 leaf
    per bucket AND travels in bf16; the default ``wire="fp32"`` keeps the
    pre-IR fp32 accumulation (bf16 is the repo's default model dtype, so
    narrower accumulation must stay opt-in)."""
    _run("""
import jax.numpy as jnp, jax
from repro.core.overlap import _make_buckets

# 6 leaves of 1024 elements; bucket budget 8 KiB.
f32 = [1024 * 4] * 6        # 4 KiB each -> 2 per bucket -> 3 buckets
bf16 = [1024 * 2] * 6       # 2 KiB each -> 4 per bucket -> 2 buckets
i8 = [1024 * 1] * 6         # 1 KiB each -> 6 fit with room -> 1 bucket
assert len(_make_buckets(f32, 8 << 10)) == 3
assert len(_make_buckets(bf16, 8 << 10)) == 2
assert len(_make_buckets(i8, 8 << 10)) == 1

# and sync_grads derives those bytes from the leaves' WIRE dtype
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.core.overlap import sync_grads
mesh = make_mesh((8,), ("data",))
leaves32 = {f"w{i}": jnp.zeros(1024, jnp.float32) for i in range(6)}
leaves16 = {f"w{i}": jnp.zeros(1024, jnp.bfloat16) for i in range(6)}
def lowered(tree, **kw):
    def f(_x):
        return sync_grads(tree, axes=("data",), mode="bucketed",
                          bucket_bytes=8 << 10, **kw)
    sf = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("data"), out_specs=P(),
                           axis_names={"data"}, check_vma=False))
    return sf.lower(jnp.zeros((8,)))
def n_ar(low):
    return low.as_text().count("stablehlo.all_reduce")
assert n_ar(lowered(leaves32)) == 3
# default: bf16 leaves upcast to fp32 (pre-IR numerics) -> fp32 sizing;
# no reduction region computes in bf16 (scalar tensor<bf16> appears only
# inside a bf16 all-reduce's region — input casts are ranked tensors)
low_def = lowered(leaves16)
assert n_ar(low_def) == 3
assert "tensor<bf16>" not in low_def.as_text()
# wire="leaf": bf16 stays bf16 -> 2 KiB/leaf sizing, bf16 on the wire
low_leaf = lowered(leaves16, wire="leaf")
assert n_ar(low_leaf) == 2
assert "tensor<bf16>" in low_leaf.as_text()
print("BUCKET-DTYPE-OK")
""")


def test_hierarchical_lowering_two_axis_counts_and_numerics():
    """Tentpole: ONE build_hierarchical schedule lowered over two mesh
    axes.  On (inter × intra) grids 2×4 and 4×2 the lowering emits
    exactly the schedule's per-stage structure — (intra-1) reduce-scatter
    ppermutes + log2(inter) butterfly ppermutes + (intra-1) allgather
    ppermutes, zero all-reduces — and agrees numerically with plain psum
    over both axes AND with the Level-A host interpretation of the SAME
    schedule object.  The compiled-HLO collective counts are
    cross-checked through repro.analysis.hlo_cost."""
    _run("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.core import lowering, tac
from repro.core import schedule as schedule_ir
from repro.core.collectives import Collectives
from repro.analysis.hlo_cost import module_cost

x = jax.random.normal(jax.random.PRNGKey(0), (8 * 500,))
want = np.asarray(x.reshape(8, -1).sum(axis=0))

for inter, intra in ((2, 4), (4, 2)):
    mesh = make_mesh((inter, intra), ("pod", "data"))
    sched = schedule_ir.build_hierarchical(intra, inter)

    def f(xl):
        return lowering.lower_allreduce(sched, xl, ("pod", "data"))
    sf = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P(("pod", "data")),
                           out_specs=P(), axis_names={"pod", "data"},
                           check_vma=False))
    got = np.asarray(sf(x))
    assert np.max(np.abs(got - want)) < 1e-3, (inter, intra)

    # psum parity
    def g(xl):
        return lowering.allreduce(xl, ("pod", "data"))
    sg = jax.jit(jax.shard_map(g, mesh=mesh, in_specs=P(("pod", "data")),
                           out_specs=P(), axis_names={"pod", "data"},
                           check_vma=False))
    np.testing.assert_allclose(got, np.asarray(sg(x)), atol=1e-3)

    # per-stage ppermute counts in the program as written
    txt = sf.lower(x).as_text()
    exp_pp = 2 * (intra - 1) + (inter.bit_length() - 1)
    assert txt.count("collective_permute") == exp_pp, (inter, intra)
    assert txt.count("all_reduce") == 0, (inter, intra)
    assert exp_pp == lowering.sends_per_rank(sched)

    # compiled-HLO cross-check via the loop-aware cost analyzer
    cost = module_cost(sf.lower(x).compile().as_text(), n_devices=8)
    assert cost.coll_counts["collective-permute"] == exp_pp, (inter, intra)
    assert cost.coll_counts["all-reduce"] == 0

    # Level-A host interpretation of the SAME schedule object
    world = tac.CommWorld(8)
    coll = Collectives(world)
    shards = [np.asarray(x).reshape(8, -1)[r] for r in range(8)]
    host = coll.run_group("allreduce", [{"value": v} for v in shards],
                          hierarchical=intra)
    for h in host:
        np.testing.assert_allclose(h, want, atol=1e-3)
print("HIER-TWO-AXIS-OK")
""")


def test_hierarchical_lowering_non_pow2_inter_uses_fused_stage():
    """Non-power-of-two pod counts keep the intra ring rounds explicit
    and lower the inter stage to ONE fused psum of the owned chunk (the
    same trade the flat non-pow2 doubling makes)."""
    r = subprocess.run(
        [sys.executable, "-c", """
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.core import lowering

mesh = make_mesh((3, 2), ("pod", "data"))
x = jax.random.normal(jax.random.PRNGKey(1), (6 * 301,))
want = np.asarray(x.reshape(6, -1).sum(axis=0))
def f(xl):
    return lowering.allreduce(xl, ("pod", "data"),
                              algorithm="hierarchical")
sf = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P(("pod", "data")),
                       out_specs=P(), axis_names={"pod", "data"},
                       check_vma=False))
assert np.max(np.abs(np.asarray(sf(x)) - want)) < 1e-3
txt = sf.lower(x).as_text()
assert txt.count("collective_permute") == 2      # intra rounds (intra=2)
assert txt.count("all_reduce") == 1              # fused inter stage
print("HIER-NONPOW2-OK")
"""],
        env=dict(_ENV, XLA_FLAGS="--xla_force_host_platform_device_count=6"),
        capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"stdout:\\n{r.stdout}\\nstderr:\\n{r.stderr}"


def test_sync_grads_hierarchical_two_axis():
    """sync_grads(hierarchical=True) reduces every bucket with the
    composed two-axis schedule: numerics match the native fused psum over
    both DP axes, buckets keep their count, and the HLO carries ppermutes
    instead of all-reduces."""
    _run("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.core.overlap import sync_grads

mesh = make_mesh((2, 4), ("pod", "data"))
n = 3000
xs = jax.random.normal(jax.random.PRNGKey(2), (8 * n,))

outs = {}
for hier in (False, True):
    def f(xl):
        out = sync_grads({"w": xl, "b": xl[:7] * 2.0},
                         axes=("pod", "data"), mode="bucketed",
                         bucket_bytes=1 << 12, hierarchical=hier)
        return out["w"], out["b"]
    sf = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P(("pod", "data")),
                           out_specs=P(), axis_names={"pod", "data"},
                           check_vma=False))
    outs[hier] = [np.asarray(o) for o in sf(xs)]
    txt = sf.lower(xs).as_text()
    if hier:
        assert txt.count("all_reduce") == 0
        assert txt.count("collective_permute") > 0
    else:
        assert txt.count("all_reduce") > 0

for a, b in zip(outs[False], outs[True]):
    np.testing.assert_allclose(a, b, atol=1e-5)

# axis-count validation
try:
    sync_grads({"w": jnp.zeros(4)}, axes=("data",), hierarchical=True)
except ValueError as e:
    assert "two DP axes" in str(e)
else:
    raise AssertionError("expected ValueError for one axis")
print("SYNC-GRADS-HIER-OK")
""")


def test_stage_impl_fused_ring_bit_parity_and_wire_counts():
    """The fused-stage ring (stage_impl=) is bit-identical to the legacy
    combine path and keeps the ppermute count; a bf16 wire keeps the
    count (pure cast), an int8 wire doubles it (payload + scale)."""
    _run("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.core import lowering
from repro.core import schedule as schedule_ir

mesh = make_mesh((8,), ("data",))
x = jax.random.normal(jax.random.PRNGKey(7), (8, 1000))
want = np.asarray(jnp.sum(x, axis=0))

def lower(alg, seg, stage_impl=None, wire=None):
    def f(xl):
        return lowering.allreduce(xl.reshape(-1), ("data",), algorithm=alg,
                                  segments=seg, stage_impl=stage_impl,
                                  wire=wire)
    return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("data"),
                             out_specs=P(), axis_names={"data"},
                             check_vma=False))

for alg, seg in (("ring", 1), ("ring", 4), ("doubling", 1)):
    base = lower(alg, seg)
    fused = lower(alg, seg, stage_impl="pallas_interpret")
    got_b = np.asarray(base(x.reshape(-1)))
    got_f = np.asarray(fused(x.reshape(-1)))
    assert np.array_equal(got_b, got_f), (alg, seg)   # bit parity, fp32
    sched = schedule_ir.build("allreduce", alg, 8, segments=seg)
    n_base = base.lower(x.reshape(-1)).as_text().count("collective_permute")
    n_fused = fused.lower(x.reshape(-1)).as_text().count(
        "collective_permute")
    assert n_base == n_fused == lowering.sends_per_rank(sched), (alg, seg)

ring = schedule_ir.build("allreduce", "ring", 8)
for wire, factor, tol in (("bf16", 1, 2e-2), ("int8", 2, 5e-2)):
    f = lower("ring", 1, stage_impl="pallas_interpret", wire=wire)
    got = np.asarray(f(x.reshape(-1)))
    rel = np.max(np.abs(got - want)) / np.max(np.abs(want))
    assert rel < tol, (wire, rel)
    n_pp = f.lower(x.reshape(-1)).as_text().count("collective_permute")
    # int8 forwards a scale alongside every payload permute
    rs = 7                       # reduce-scatter rounds (n-1)
    expect = lowering.sends_per_rank(ring) + (rs + 7) * (factor - 1)
    assert n_pp == expect, (wire, n_pp, expect)
print("stage parity OK")
""")


def test_stage_impl_option_validation():
    _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import lowering
for bad in (dict(algorithm="native", stage_impl="ref"),
            dict(algorithm="native", wire="bf16"),
            dict(algorithm="doubling", stage_impl="ref", wire="bf16"),
            dict(algorithm="ring", wire="bf16")):        # wire w/o stage
    try:
        lowering.allreduce(jnp.zeros(8), ("data",), **bad)
    except ValueError:
        pass
    else:
        raise AssertionError(f"accepted {bad}")
try:
    lowering.allreduce(jnp.zeros(8), ("data",), algorithm="ring",
                       stage_impl="nope")
except ValueError:
    pass
else:
    raise AssertionError("accepted bogus stage_impl")
print("validation OK")
""")


def test_sync_grads_stage_tier_passthrough():
    """sync_grads(stage_impl=) is bit-identical to the plain ring path;
    stage_wire="bf16" narrows the wire within bf16 tolerance; combining
    compress="int8" with the stage tier is rejected."""
    _run("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.core import overlap

mesh = make_mesh((8,), ("data",))
g = {"w": jax.random.normal(jax.random.PRNGKey(3), (8, 64, 17))}

def run(**kw):
    def f(gl):
        return overlap.sync_grads(gl, axes=("data",), algorithm="ring",
                                  mean=False, **kw)
    sf = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("data"), out_specs=P(),
                           axis_names={"data"}, check_vma=False))
    return np.asarray(sf(g)["w"])

base = run()
assert np.array_equal(base, run(stage_impl="pallas_interpret"))
want = np.asarray(jnp.sum(g["w"], axis=0))
rel = np.max(np.abs(run(stage_impl="pallas_interpret", stage_wire="bf16")
                    - want)) / np.max(np.abs(want))
assert rel < 2e-2, rel
try:
    run(compress="int8", stage_impl="pallas_interpret")
except ValueError:
    pass
else:
    raise AssertionError("compress=int8 + stage tier accepted")
print("sync_grads stage OK")
""")
