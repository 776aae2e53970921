"""Distribution correctness, run in subprocesses with 8 host devices:

1. a GraphSession under the fused device policy, placed on the 2D mesh
   (jobs over one axis, vertex blocks over the other), reaches the same
   PageRank fixpoint as the single-device run;
2. a checkpoint saved under one mesh restores onto a different mesh
   (elastic re-shard) bit-exactly.
"""

import os
import subprocess
import sys

ENGINE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax
from repro.algorithms import PageRank, PersonalizedPageRank
from repro.core import Fused, GraphSession
from repro.dist.mesh2d import make_mesh2d
from repro.graph import rmat_graph

csr = rmat_graph(256, 5, seed=21)
algs = [PageRank(), PageRank(damping=0.7),
        PersonalizedPageRank(source=3), PersonalizedPageRank(source=99)]


def run(mesh):
    sess = GraphSession(csr, 16, capacity=len(algs), seed=0)
    handles = [sess.submit(a) for a in algs]
    m = sess.run(Fused(), 20000, mesh=mesh)
    assert m.converged
    return sess, np.stack([sess.result(h) for h in handles])


_, ref = run(None)                     # single-device reference
# sharded: jobs over one mesh axis, vertex blocks over the other
sess, out = run(make_mesh2d(jobs=2, blocks=4))
assert sess._mesh2d is not None
assert len(sess.view_groups()[0].values.sharding.device_set) == 8
np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-7)
print("DIST-ENGINE-OK")
"""

ELASTIC_SCRIPT = r"""
import os, tempfile
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.train.checkpoint import save_checkpoint, restore_checkpoint

d = tempfile.mkdtemp()
mesh_a = jax.make_mesh((8,), ("data",))
tree = {"w": jax.device_put(jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
                            NamedSharding(mesh_a, P("data", None))),
        "s": jnp.int32(7)}
save_checkpoint(d, 5, tree)

# restore onto a DIFFERENT mesh shape (elastic rescale 8 -> 2x4)
mesh_b = jax.make_mesh((2, 4), ("data", "model"))
like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
sh = {"w": NamedSharding(mesh_b, P("model", "data")),
      "s": NamedSharding(mesh_b, P())}
restored, step = restore_checkpoint(d, like, sh)
assert step == 5
np.testing.assert_array_equal(np.asarray(restored["w"]),
                              np.arange(64, dtype=np.float32).reshape(8, 8))
assert restored["w"].sharding.spec == P("model", "data")
print("ELASTIC-OK")
"""


def _run(script, marker):
    result = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True, timeout=480,
        env={**os.environ, "PYTHONPATH": "src"})
    assert marker in result.stdout, result.stderr[-2000:]


def test_fused_engine_sharded_matches_single_device():
    _run(ENGINE_SCRIPT, "DIST-ENGINE-OK")


def test_elastic_checkpoint_reshard():
    _run(ELASTIC_SCRIPT, "ELASTIC-OK")
