"""Gradient-compression tests (core/overlap.py) — subprocess: 8 devices."""

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


def test_quantized_reduction_accuracy_and_wire_dtype():
    """int8 reduction ≈ exact mean (1%% of max) and the wire collectives
    (all-to-all / all-gather) carry s8 tensors; bf16 halves the all-reduce
    payload."""
    _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.core.overlap import quantized_psum_mean, sync_grads

mesh = make_mesh((8,), ("data",))
n = 4096
xs = jax.random.normal(jax.random.PRNGKey(0), (8, n)) * \\
    jnp.linspace(0.1, 3.0, 8)[:, None]      # heterogeneous scales

def f(x_local):
    return quantized_psum_mean(x_local.reshape(-1), "data")

sf = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("data"),
                       out_specs=P(), axis_names={"data"},
                       check_vma=False))
got = np.asarray(sf(xs.reshape(-1)))
exact = np.asarray(jnp.mean(xs, axis=0))
tol = float(jnp.max(jnp.abs(xs))) / 127.0 * 2.1   # two quantisation legs
assert np.max(np.abs(got - exact)) < tol, (np.max(np.abs(got - exact)), tol)

txt = sf.lower(xs.reshape(-1)).compile().as_text()
assert "s8[" in txt, "int8 tensors must be on the wire"

# bf16 compression path through sync_grads
def g(x_local):
    out = sync_grads({"w": x_local}, axes=("data",), mode="fused",
                     compress="bf16")
    return out["w"]
sg = jax.jit(jax.shard_map(g, mesh=mesh, in_specs=P("data"),
                       out_specs=P(), axis_names={"data"},
                       check_vma=False))
got_bf = np.asarray(sg(xs.reshape(-1)))
assert np.max(np.abs(got_bf - exact)) < 0.05
assert "bf16[" in sg.lower(xs.reshape(-1)).compile().as_text()
print("COMPRESSION-OK")
""")


def test_quantized_psum_error_bound_per_leg():
    """The documented bound: each quantisation leg contributes at most
    max|x|/127 per element — the reduce-scatter leg bounded by the max
    input magnitude, the all-gather leg by the max of the (mean-reduced)
    partials, so the end-to-end error is <= (max|x| + max|mean|)/127."""
    _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.core.overlap import quantized_psum_mean

mesh = make_mesh((8,), ("data",))
n = 8192
xs = jax.random.normal(jax.random.PRNGKey(7), (8, n)) * \\
    jnp.linspace(0.2, 5.0, 8)[:, None]

def f(x_local):
    return quantized_psum_mean(x_local.reshape(-1), "data")
sf = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("data"), out_specs=P(),
                       axis_names={"data"}, check_vma=False))
got = np.asarray(sf(xs.reshape(-1)))
exact = np.asarray(jnp.mean(xs, axis=0))
bound = (float(jnp.max(jnp.abs(xs))) + float(jnp.max(jnp.abs(exact)))) \\
    / 127.0
err = np.max(np.abs(got - exact))
assert err <= bound * 1.0001, (err, bound)
# the bound is tight-ish: a constant input quantises exactly
ones = jnp.ones((8 * n,))
exact0 = np.asarray(sf(ones))
assert np.max(np.abs(exact0 - 1.0)) < 1e-6
print("BOUND-OK", err, bound)
""")


def test_quantized_psum_padding_non_divisible():
    """Sizes with n % world != 0 round-trip through the pad/unpad path
    with the same error bound and exact shape."""
    _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.core.overlap import quantized_psum_mean

mesh = make_mesh((8,), ("data",))
for n in (4097, 1001, 17, 8):          # 8 % 8 == 0 control included
    xs = jax.random.normal(jax.random.PRNGKey(n), (8, n))
    def f(x_local):
        return quantized_psum_mean(x_local.reshape(-1), "data")
    sf = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("data"), out_specs=P(),
                           axis_names={"data"}, check_vma=False))
    got = np.asarray(sf(xs.reshape(-1)))
    exact = np.asarray(jnp.mean(xs, axis=0))
    assert got.shape == (n,), (n, got.shape)
    tol = float(jnp.max(jnp.abs(xs))) / 127.0 * 2.1
    assert np.max(np.abs(got - exact)) < tol, n
print("PADDING-OK")
""")


def test_quantized_psum_round_trip_vs_fp32_psum():
    """End-to-end: one sync_grads step with compress="int8" agrees with
    the uncompressed fp32 psum path within the two-leg bound."""
    _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.core.overlap import sync_grads

mesh = make_mesh((8,), ("data",))
n = 2048
xs = jax.random.normal(jax.random.PRNGKey(3), (8, n))

def make(compress):
    def f(x_local):
        out = sync_grads({"w": x_local}, axes=("data",), mode="fused",
                         compress=compress)
        return out["w"]
    return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("data"),
                             out_specs=P(), axis_names={"data"},
                             check_vma=False))

ref = np.asarray(make(None)(xs.reshape(-1)))       # fp32 psum mean
q = np.asarray(make("int8")(xs.reshape(-1)))
tol = float(jnp.max(jnp.abs(xs))) / 127.0 * 2.1
assert np.max(np.abs(q - ref)) < tol, np.max(np.abs(q - ref))
print("ROUND-TRIP-OK")
""")
