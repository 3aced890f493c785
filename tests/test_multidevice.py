"""Multi-device integration: the real train/serve paths on an 8-fake-device
host mesh (subprocess so the device-count flag can't leak into other tests)."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
assert len(jax.devices()) == 8
from repro.configs import get_config
from repro.launch import sharding, step as step_mod
from repro.models import registry, transformer
from repro.models.common import ModelCtx
from repro.optim.adamw import adamw

from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
cfg = get_config("llama3.2-3b").reduced()
sp = transformer.build_specs(cfg)
opt = adamw(3e-3)
params = transformer.init(jax.random.PRNGKey(0), cfg)
opt_state = opt.init(params)
ps = sharding.param_shardings(mesh, params)
os_ = sharding.opt_state_shardings(mesh, opt_state, ps)
params = jax.device_put(params, ps)
opt_state = jax.device_put(opt_state, os_)
ctx = ModelCtx(mode="train", act_dp=("data",), attn_cp="model")
step = step_mod.make_train_step(cfg, sp, opt, ctx=ctx, grad_shardings=ps)
jstep = jax.jit(step, donate_argnums=(0, 1))
losses = []
with mesh:
    for i in range(8):
        batch = registry.make_batch(jax.random.PRNGKey(i), cfg, 8, 64)
        params, opt_state, m = jstep(params, opt_state, batch, jax.random.PRNGKey(i))
        losses.append(float(m["loss"]))
assert all(np.isfinite(l) for l in losses), losses
assert losses[-1] < losses[0], losses
print("MULTIDEVICE_TRAIN_OK", losses[0], "->", losses[-1])
'''


def test_train_on_8_device_mesh():
    env = {**os.environ, "PYTHONPATH": os.path.join(REPO, "src")}
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, env=env, cwd=REPO, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "MULTIDEVICE_TRAIN_OK" in r.stdout
