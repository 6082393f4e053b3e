"""GMI collectives + pipeline + compressed psum on 8 simulated devices.

Multi-device tests run in a subprocess (XLA_FLAGS must be set before jax
init and must NOT leak into the 1-device test session, per the brief).
"""
import os
import subprocess
import sys
import textwrap

import pytest


def _run(script: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return out.stdout


PRELUDE = """
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core import gmi
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("pod", "data"))
x = jnp.arange(8 * 4, dtype=jnp.float32).reshape(8, 4) + 1.0
def run(fn, in_spec, out_spec):
    return jax.shard_map(fn, mesh=mesh, in_specs=(in_spec,),
                         out_specs=out_spec, check_vma=False)
"""


def test_gmi_primitives_and_composition():
    _run(PRELUDE + """
# broadcast: every member sees root's shard
y = run(lambda v: gmi.broadcast(v, "data", root=2),
        P(("pod", "data")), P("pod", None))(x)
assert np.allclose(np.asarray(y)[0], np.asarray(x)[2])
assert np.allclose(np.asarray(y)[1], np.asarray(x)[6])

# reduce: only root holds the sum
r = run(lambda v: gmi.reduce(v, "data", root=1), P(("pod","data")), P(("pod","data")))(x)
r = np.asarray(r)
assert np.allclose(r[1], np.asarray(x)[:4].sum(0))
assert np.allclose(r[0], 0) and np.allclose(r[2], 0)

# scatter: member i of the group receives slice i of root's (4,...) value
# (per-member value is a (4,) row; the out_spec stacks them -> (32,))
s_in = jnp.arange(4 * 4, dtype=jnp.float32).reshape(4, 4)
sc = run(lambda v: gmi.scatter(v, "data", root=0), P(), P(("pod","data")))(s_in)
assert np.allclose(np.asarray(sc).reshape(8, 4),
                   np.concatenate([np.asarray(s_in)] * 2, 0))

# composed == fused (paper: AllGather = Gather -> Broadcast, etc.)
a1 = run(lambda v: gmi.allreduce_composed(v, "data"), P(("pod","data")), P("pod", None))(x)
a2 = run(lambda v: gmi.allreduce(v, "data"), P(("pod","data")), P("pod", None))(x)
assert np.allclose(np.asarray(a1), np.asarray(a2))
g1 = run(lambda v: gmi.allgather_composed(v, "data"), P(("pod","data")), P("pod", None, None))(x)
g2 = run(lambda v: gmi.allgather(v, "data"), P(("pod","data")), P("pod", None, None))(x)
assert np.allclose(np.asarray(g1), np.asarray(g2))
print("OK")
""")


def test_hierarchical_gateway_allreduce():
    _run(PRELUDE + """
# hierarchical (gateway) == flat; and cluster_send rotates along pods
h1 = run(lambda v: gmi.hier_allreduce(v, "data", "pod"), P(("pod","data")), P(None))(x)
h2 = run(lambda v: gmi.flat_allreduce(v, "data", "pod"), P(("pod","data")), P(None))(x)
assert np.allclose(np.asarray(h1), np.asarray(h2))

snd = run(lambda v: gmi.cluster_send(v, "pod"), P("pod", None), P("pod", None))(x)
assert np.allclose(np.asarray(snd)[:4], np.asarray(x)[4:])
assert np.allclose(np.asarray(snd)[4:], np.asarray(x)[:4])
print("OK")
""")


def test_compressed_psum_close_to_exact():
    _run(PRELUDE + """
from repro.optim.compression import compressed_psum
g = jnp.asarray(np.random.default_rng(0).normal(0, 1, (8, 64)).astype(np.float32))
exact = run(lambda v: jax.lax.psum(v, "pod"), P(("pod","data")), P(("pod","data")))(g)
approx = run(lambda v: compressed_psum(v, "pod"), P(("pod","data")), P(("pod","data")))(g)
err = np.abs(np.asarray(exact) - np.asarray(approx))
scale = np.abs(np.asarray(exact)).max()
assert err.max() <= 2 * scale / 127 + 1e-6, err.max()
print("OK")
""")


def test_pipeline_matches_sequential():
    _run("""
import numpy as np, jax, jax.numpy as jnp
from repro.core.pipeline import pipelined_apply, pipeline_steps
from repro.launch.mesh import make_mesh
mesh = make_mesh((4,), ("stage",))
w = jnp.asarray(np.random.default_rng(0).normal(0, 0.5, (4, 8, 8)).astype(np.float32))
xm = jnp.asarray(np.random.default_rng(1).normal(0, 1, (6, 2, 8)).astype(np.float32))
out = pipelined_apply(lambda p, v: jnp.tanh(v @ p), mesh, "stage", w, xm)
ref = xm
for s in range(4):
    ref = jnp.tanh(ref @ w[s])
assert np.allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
assert pipeline_steps(6, 4) == 9
print("OK")
""")
