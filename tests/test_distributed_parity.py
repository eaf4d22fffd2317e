"""Distribution correctness: sharded training/serving == single-device.

The strongest evidence the FSDP x TP policy + activation constraints are
semantics-preserving: the same reduced model, same data, trained 5 steps on
a (2 data x 4 model) mesh with the full sharding policy vs unsharded — the
loss trajectories must match to float tolerance. Runs in a subprocess with
8 forced host devices."""
import pytest

import os
import subprocess
import sys

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

pytestmark = pytest.mark.slow

CODE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.data.pipeline import MarkovTokenDataset
from repro.models import build_model
from repro.sharding import policy
from repro.training import optimizer, train_loop

cfg = get_config("qwen2-1.5b").reduced(layers=2, d_model=128, vocab=512)
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
ds = MarkovTokenDataset(vocab_size=512, seq_len=32, batch_size=8)
batches = [b for b, _ in zip(ds.batches(), range(5))]
opt_cfg = optimizer.AdamWConfig(total_steps=5, warmup_steps=1)

def run(sharded):
    p = jax.tree.map(jnp.copy, params)   # train_step donates its args
    o = optimizer.init(p)
    losses = []
    if sharded:
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        p_sh = policy.to_shardings(policy.param_specs(p, mesh), mesh)
        o_sh = policy.to_shardings(policy.param_specs(o, mesh), mesh)
        p = jax.device_put(p, p_sh)
        o = jax.device_put(o, o_sh)
        step = train_loop.make_train_step(model, opt_cfg, jit=True)
        with mesh, policy.activation_policy(mesh):
            for b in batches:
                b_sh = policy.to_shardings(policy.batch_specs(b, mesh), mesh)
                b = jax.device_put(b, b_sh)
                p, o, m = step(p, o, b)
                losses.append(float(m["loss"]))
    else:
        step = train_loop.make_train_step(model, opt_cfg, jit=True)
        for b in batches:
            p, o, m = step(p, o, b)
            losses.append(float(m["loss"]))
    return losses, p

l1, p1 = run(False)
l2, p2 = run(True)
print("single:", [f"{x:.6f}" for x in l1])
print("sharded:", [f"{x:.6f}" for x in l2])
np.testing.assert_allclose(l1, l2, rtol=2e-4, atol=2e-4)
d = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
print("max param diff:", d)
assert d < 5e-3, d
print("PARITY_OK")
"""


def test_sharded_training_matches_single_device():
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", CODE], capture_output=True,
                         text=True, env=env, timeout=560)
    assert out.returncode == 0, (out.stdout[-1500:], out.stderr[-2500:])
    assert "PARITY_OK" in out.stdout, out.stdout
