"""GPipe pipeline parallelism: schedule equivalence vs sequential execution.

Runs in a subprocess with 8 fake host devices (XLA_FLAGS before jax import).
"""

import json
import os
import subprocess
import sys

import pytest

from repro.sharding.pipeline import bubble_fraction

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp, numpy as np
from repro.sharding.pipeline import pipeline_apply

from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("pipe", "data"))
S, LPS, M, MB, D = 4, 2, 6, 3, 16  # stages, layers/stage, microbatches, mb size, width
rng = np.random.default_rng(0)
Ws = jnp.asarray(rng.standard_normal((S, LPS, D, D)) * 0.3, dtype=jnp.float32)
bs = jnp.asarray(rng.standard_normal((S, LPS, D)) * 0.1, dtype=jnp.float32)
x = jnp.asarray(rng.standard_normal((M, MB, D)), dtype=jnp.float32)

def stage_fn(params, h):
    W, b = params
    def layer(h, wb):
        w, bb = wb
        return jnp.tanh(h @ w + bb), None
    h, _ = jax.lax.scan(layer, h, (W, b))
    return h

# sequential reference: all S*LPS layers in order
def reference(x):
    h = x
    for s in range(S):
        h = stage_fn((Ws[s], bs[s]), h)
    return h

with mesh:
    out = pipeline_apply(stage_fn, (Ws, bs), x, mesh, axis="pipe")
ref = jax.vmap(reference)(x.reshape(M, MB, D)).reshape(M, MB, D) if False else reference(x)
err = float(jnp.abs(out - ref).max())
print("RESULTS:" + json.dumps({"err": err}))
"""


class TestPipeline:
    def test_bubble_fraction(self):
        assert bubble_fraction(4, 6) == pytest.approx(3 / 9)
        assert bubble_fraction(1, 8) == 0.0

    def test_schedule_matches_sequential(self):
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
        proc = subprocess.run(
            [sys.executable, "-c", _SCRIPT], capture_output=True, text=True, env=env, timeout=600
        )
        if proc.returncode != 0:  # the child's whole stderr, not a tail
            pytest.fail(f"child exited {proc.returncode}:\n{proc.stderr}", pytrace=False)
        line = [l for l in proc.stdout.splitlines() if l.startswith("RESULTS:")][0]
        res = json.loads(line[len("RESULTS:"):])
        assert res["err"] < 1e-5, res
