"""True multi-device distribution semantics, in a subprocess with 8 fake
host devices (XLA_FLAGS must be set before jax import, so not in-process)."""

import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_smoke_config
from repro.launch.steps import make_train_step
from repro.models.model import build_model
from repro.optim.adamw import AdamW
from repro.optim.grad_compress import compressed_psum
from repro.sharding.rules import batch_pspec, cache_pspecs, param_pspecs, to_shardings

results = {}
from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
cfg = get_smoke_config("qwen2-0.5b")
bundle = build_model(cfg)
opt = AdamW(warmup_steps=2)

params = bundle.init(jax.random.PRNGKey(0))
p_spec = param_pspecs(jax.eval_shape(bundle.init, jax.random.PRNGKey(0)), mesh)
p_sh = to_shardings(p_spec, mesh)
opt_state = opt.init(params)
o_sh = to_shardings(opt.state_pspecs(p_spec), mesh)
batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab)}
b_sh = to_shardings(batch_pspec(batch, mesh), mesh)

params = jax.device_put(params, p_sh)
opt_state = jax.device_put(opt_state, o_sh)
batch = jax.device_put(batch, b_sh)

step = jax.jit(make_train_step(bundle, opt), in_shardings=(p_sh, o_sh, b_sh))
with mesh:
    # distributed loss must equal single-device loss
    loss_dist = float(step(params, opt_state, batch)[2])
results["loss_dist"] = loss_dist

# single-device reference
params_1 = bundle.init(jax.random.PRNGKey(0))
loss_ref = float(bundle.loss(params_1, {"tokens": np.asarray(batch["tokens"])}))
results["loss_ref"] = loss_ref

# compressed integer all-reduce (shard_map collective)
x = jnp.asarray(np.random.default_rng(0).standard_normal(64), dtype=jnp.float32)
with mesh:
    out = compressed_psum(x, mesh, axis="data", bits=12, E_rel=1e-2)
results["psum_err"] = float(jnp.abs(out - x).max())
results["psum_bound"] = float(1e-2 * jnp.abs(x).max())

# decode step with sharded cache
cache = bundle.init_cache(8, 16)
c_sh = to_shardings(cache_pspecs(jax.eval_shape(lambda: bundle.init_cache(8, 16)), mesh), mesh)
cache = jax.device_put(cache, c_sh)
tok = jnp.zeros((8, 1), dtype=jnp.int32)
with mesh:
    logits, cache = jax.jit(bundle.decode, in_shardings=(p_sh, None, c_sh))(params, tok, cache)
results["decode_finite"] = bool(np.isfinite(np.asarray(logits, dtype=np.float32)).all())

print("RESULTS:" + json.dumps(results))
"""


@pytest.fixture(scope="module")
def dist_results():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT], capture_output=True, text=True, env=env, timeout=600
    )
    if proc.returncode != 0:  # the child's whole stderr, not a tail
        pytest.fail(f"child exited {proc.returncode}:\n{proc.stderr}", pytrace=False)
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULTS:")][0]
    return json.loads(line[len("RESULTS:"):])


class TestDistributed:
    def test_distributed_loss_matches_single_device(self, dist_results):
        assert abs(dist_results["loss_dist"] - dist_results["loss_ref"]) < 5e-2

    def test_compressed_psum_bound(self, dist_results):
        # single participant => psum mean == dequantized value; error <= E
        assert dist_results["psum_err"] <= dist_results["psum_bound"] * 1.01

    def test_sharded_decode_runs(self, dist_results):
        assert dist_results["decode_finite"]
