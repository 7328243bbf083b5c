"""Sharded == unsharded numerics: the full train step under the production
sharding rules on a small (2×4) forced-host-device mesh must match the
single-device step bit-for-bit-ish.  Run in a subprocess because the device
count must be fixed before jax initialises."""
import os
import subprocess
import sys

import pytest

CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_tiny_config
from repro.data import pipeline
from repro.distributed.sharding import ShardingRules
from repro.launch.mesh import _make_mesh
from repro.launch.steps import init_train_state, make_train_step
from repro.training import optim

cfg = get_tiny_config("{arch}").replace(dtype="float32", d_model=256, d_ff=512)
# _make_mesh: Auto axis types (jax.make_mesh defaults to Explicit)
mesh = _make_mesh((2, 4), ("data", "model"))
rules = ShardingRules(cfg, mesh, mode="train")

data = pipeline.for_config(cfg, 32, 8)
batch = data.batch(0, 0)
state = init_train_state(cfg, jax.random.PRNGKey(0))

# unsharded reference
ref_step = jax.jit(make_train_step(cfg))
ref_state, ref_m = ref_step(state, batch)

# sharded: same fn + constraints + explicit in_shardings
state2 = init_train_state(cfg, jax.random.PRNGKey(0))
p_spec = rules.params_tree(jax.eval_shape(lambda: state2["params"]))
state_spec = {{"params": p_spec, "opt": optim.OptState(step=P(), m=p_spec, v=p_spec)}}
state_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), state_spec,
                        is_leaf=lambda x: isinstance(x, P))
batch_spec = {{k: NamedSharding(mesh, rules.batch_spec(v.shape))
              for k, v in batch.items()}}
sh_step = jax.jit(make_train_step(cfg, constrain=rules.constrain),
                  in_shardings=(state_sh, batch_spec),
                  out_shardings=(state_sh, None))
sh_state, sh_m = sh_step(state2, batch)

assert abs(float(ref_m["loss"]) - float(sh_m["loss"])) < 1e-4, (
    float(ref_m["loss"]), float(sh_m["loss"]))
diffs = [float(jnp.max(jnp.abs(a - b)))
         for a, b in zip(jax.tree_util.tree_leaves(ref_state["params"]),
                         jax.tree_util.tree_leaves(sh_state["params"]))]
assert max(diffs) < 2e-4, max(diffs)
print("EQUIV_OK", float(ref_m["loss"]), max(diffs))
"""


# each case is a fresh interpreter compiling two full train steps on 8
# forced host devices — minutes per arch on CI, so the whole module sits
# behind the distributed (and slow) markers: `make test` skips it,
# `make test-distributed` (or plain tier-1 `pytest`) runs it
pytestmark = [pytest.mark.distributed, pytest.mark.slow]


@pytest.mark.parametrize("arch", ["llama3-8b", "dbrx-132b", "mamba2-780m"])
def test_sharded_train_step_matches_unsharded(arch):
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("JAX_PLATFORMS", None)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", CODE.format(arch=arch)],
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))),
                         env=env, capture_output=True, text=True,
                         timeout=900)
    assert "EQUIV_OK" in out.stdout, (out.stdout[-1000:], out.stderr[-3000:])
