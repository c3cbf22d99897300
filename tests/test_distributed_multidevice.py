"""Distributed behaviors on a real (host-platform) multi-device mesh.

Run in subprocesses so the main pytest process keeps its single device.
Covers: int8-compressed cross-pod gradient psum inside shard_map, elastic
re-meshing 8 -> 4 devices with parameter re-sharding, and FSDP param
placement on a 2x2 mesh.
"""

import os
import subprocess
import sys

COMPRESSED_PSUM = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, PartitionSpec as P
from repro.optim import compression

mesh = jax.make_mesh((4,), ("pod",), axis_types=(AxisType.Auto,))
g = jax.random.normal(jax.random.PRNGKey(0), (4, 2048)) * 0.01
err = jnp.zeros_like(g)

def body(g, err):
    out, new_err = compression.compressed_psum(g[0], err[0], "pod")
    return out[None], new_err[None]

fn = jax.shard_map(body, mesh=mesh, in_specs=(P("pod"), P("pod")), out_specs=(P("pod"), P("pod")))
out, new_err = fn(g, err)
want = np.asarray(g).sum(0)
got = np.asarray(out)[0]
rel = np.abs(got - want).max() / (np.abs(want).max() + 1e-9)
assert rel < 0.02, rel   # int8 grid error, bounded
# all pods agree on the reduced value
assert np.allclose(np.asarray(out)[0], np.asarray(out)[1])
print("COMPRESSED_PSUM_OK", rel)
"""

ELASTIC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.runtime import elastic
from repro.runtime.sharding import param_shardings

devs = jax.devices()
mesh8 = elastic.remesh(devs, 2)          # (4, 2) data x model
params = {"layers": {"mlp": {"up": {"w": jnp.arange(64.0).reshape(8, 8)}}}}
sh8 = param_shardings(mesh8, params)
p8 = jax.device_put(params, sh8)
# lose half the fleet: re-mesh onto 4 devices, model axis preserved
mesh4 = elastic.remesh(devs[:4], 2)      # (2, 2)
p4 = elastic.reshard_state(p8, mesh4)
np.testing.assert_array_equal(np.asarray(p4["layers"]["mlp"]["up"]["w"]),
                              np.arange(64.0).reshape(8, 8))
assert len(p4["layers"]["mlp"]["up"]["w"].sharding.mesh.devices.ravel()) == 4
print("ELASTIC_OK")
"""

FSDP = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.runtime.sharding import param_shardings
mesh = jax.make_mesh((2, 2), ("data", "model"))
params = {"layers": {"attn": {"wqkv": {"w": jnp.zeros((4, 8, 16))}}}}
sh = param_shardings(mesh, params, fsdp=True)
spec = sh["layers"]["attn"]["wqkv"]["w"].spec
assert spec == jax.sharding.PartitionSpec(None, "data", "model"), spec
p = jax.device_put(params, sh)
shard_shape = p["layers"]["attn"]["wqkv"]["w"].addressable_shards[0].data.shape
assert shard_shape == (4, 4, 8), shard_shape  # sharded both ways
print("FSDP_OK")
"""


def _run(script: str, token: str):
    env = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"}
    # keep the platform pin: without it a TPU-plugin host spins on GCP
    # metadata queries inside the hermetic subprocess
    for var in ("JAX_PLATFORMS", "TPU_SKIP_MDS_QUERY", "HOME"):
        if var in os.environ:
            env[var] = os.environ[var]
    r = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        text=True,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=600,
    )
    assert token in r.stdout, (r.stdout, r.stderr[-2000:])


def test_compressed_psum_multidevice():
    _run(COMPRESSED_PSUM, "COMPRESSED_PSUM_OK")


def test_elastic_remesh_multidevice():
    _run(ELASTIC, "ELASTIC_OK")


def test_fsdp_placement_multidevice():
    _run(FSDP, "FSDP_OK")
