"""Expert-parallel MoE (shard_map all-to-all) vs the TP reference path.

Needs >1 device, so it runs in a subprocess with forced host devices."""
import pytest

import os
import subprocess
import sys

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

pytestmark = pytest.mark.slow

CODE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import ModelConfig, MOE
from repro.models import blocks
from repro.sharding import policy

cfg_tp = ModelConfig(name="t", family="moe", num_layers=1, d_model=64,
                     num_heads=2, num_kv_heads=2, head_dim=32, d_ff=64,
                     vocab_size=64, group_pattern=(MOE,), num_experts=4,
                     num_experts_per_tok=2, moe_capacity_factor=4.0,
                     dtype="float32")
cfg_ep = dataclasses.replace(cfg_tp, moe_ep_shards=2)

key = jax.random.PRNGKey(0)
p_tp = blocks._init_moe(key, cfg_tp)
p_ep = blocks._init_moe(key, cfg_ep)
# same logical weights: convert TP -> EP layout explicitly
e, d, f, r = 4, 64, 64, 2
fr = f // r
we = p_tp["experts"]
p_ep["experts"] = {
    "ep_gate": we["w_gate"].reshape(e, d, r, fr).transpose(0, 2, 1, 3)
    .reshape(e * r, d, fr),
    "ep_up": we["w_up"].reshape(e, d, r, fr).transpose(0, 2, 1, 3)
    .reshape(e * r, d, fr),
    "ep_down": we["w_down"].reshape(e, r, fr, d).reshape(e * r, fr, d),
}
p_ep["router"] = p_tp["router"]
p_ep["moe_norm"] = p_tp["moe_norm"]

x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 64))

# reference: TP path on one device
y_tp, aux_tp = blocks._moe_ffn(p_tp, x, cfg_tp)

# EP path under a (1, 8) mesh
mesh = jax.make_mesh((1, 8), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
with mesh, policy.activation_policy(mesh):
    y_ep, aux_ep = jax.jit(lambda p, x: blocks._moe_ffn(p, x, cfg_ep))(p_ep, x)

err = float(jnp.max(jnp.abs(y_tp - y_ep)))
print("max_err", err, "aux", float(aux_tp), float(aux_ep))
assert err < 2e-4, err
# aux load-balance metric: same order (EP is an inference layout; aux only
# regularises training, where the TP path is used)
import math as _math
assert _math.isfinite(float(aux_ep)) and float(aux_ep) > 0.5

# EP fallback path (no mesh) must also match
y_fb, _ = blocks._moe_ffn(p_ep, x, cfg_ep)
err2 = float(jnp.max(jnp.abs(y_tp - y_fb)))
print("fallback_err", err2)
assert err2 < 2e-4, err2
print("EP_OK")
"""


def test_ep_moe_matches_tp_reference():
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", CODE], capture_output=True,
                         text=True, env=env, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "EP_OK" in out.stdout, out.stdout
