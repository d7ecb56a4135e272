"""Multi-host backend test: a REAL two-process jax.distributed cluster on
localhost CPU (the standard stand-in for a multi-host pod, same shape as the
virtual-device mesh tests but with actual cross-process collectives).

Each subprocess exposes 2 virtual CPU devices -> a 4-device global mesh
over 2 processes; the test runs a global-sum over a dp-sharded array whose
shards live on DIFFERENT processes, so the psum crosses the process
boundary through the distributed runtime.
"""

import os
import socket
import subprocess
import sys

_WORKER = r"""
import sys
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from dalle_pytorch_tpu.parallel import make_mesh
from dalle_pytorch_tpu.parallel.multihost import initialize, is_primary

port, pid = sys.argv[1], int(sys.argv[2])
assert initialize(coordinator_address=f"127.0.0.1:{port}",
                  num_processes=2, process_id=pid)
assert jax.process_count() == 2, jax.process_count()
assert len(jax.devices()) == 4, jax.devices()
assert is_primary() == (pid == 0)

mesh = make_mesh({"dp": 4})
sharding = NamedSharding(mesh, P("dp"))
# each process contributes DIFFERENT local data: process p holds 2 elements
# of value p+1 -> global array [1,1,2,2], sum 6
local = np.full((2,), pid + 1, np.float32)
arr = jax.make_array_from_process_local_data(sharding, local, (4,))
total = jax.jit(jnp.sum, out_shardings=NamedSharding(mesh, P()))(arr)
print(f"RESULT {float(total)}", flush=True)

# the CLI data path: shard_batch assembles per-host LOCAL batches into the
# global batch, and one sharded train step crosses the process boundary
import optax
from dalle_pytorch_tpu.parallel import shard_batch
from dalle_pytorch_tpu.parallel.train import make_train_step, setup_sharded

params = {"w": jnp.full((2,), 2.0)}
opt = optax.sgd(0.1)
params, opt_state = setup_sharded(params, opt, mesh)
step = make_train_step(
    lambda p, b, r: jnp.mean(jnp.sum(b["x"] * p["w"], -1)), opt)
batch = shard_batch(mesh, {"x": np.full((2, 2), pid + 1.0, np.float32)})
# global batch rows: [1,1],[1,1],[2,2],[2,2]; row sums x w=2 -> [4,4,8,8]
params, opt_state, loss = step(params, opt_state, batch,
                               jax.random.PRNGKey(0))
print(f"RESULT2 {float(loss)}", flush=True)    # mean = 6.0

# checkpoint gate: both processes call save; only process 0 writes. The
# collective after the save is a barrier: process 0's (synchronous) write
# is complete before process 1 can pass it and check the directory.
import os
from dalle_pytorch_tpu import checkpoint as ckpt
path = os.path.join(sys.argv[3], "mh-ckpt")
ckpt.save(path, jax.device_get(params), step=1)
float(jax.jit(jnp.sum, out_shardings=NamedSharding(mesh, P()))(arr))
print(f"RESULT3 {os.path.isdir(path)}", flush=True)
"""


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_two_process_cluster_global_sum(tmp_path):
    port = _free_port()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    procs = [
        subprocess.Popen([sys.executable, "-c", _WORKER, str(port), str(p),
                          str(tmp_path)],
                         cwd=repo, env=env, stdout=subprocess.PIPE,
                         stderr=subprocess.STDOUT, text=True)
        for p in range(2)
    ]
    outs = [p.communicate(timeout=240)[0] for p in procs]
    for p, out in zip(procs, outs):
        assert p.returncode == 0, f"worker failed:\n{out}"
        assert "RESULT 6.0" in out, out
        assert "RESULT2 6.0" in out, out
        assert "RESULT3 True" in out, out
    # the checkpoint was written exactly once (no .ckpt-tmp- residue from a
    # second racing writer)
    residue = [d for d in os.listdir(tmp_path) if d.startswith(".ckpt-tmp-")]
    assert not residue, residue
