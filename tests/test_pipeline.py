"""Pipeline parallelism: shard_map GPipe schedule == sequential stages
(subprocess: needs >1 fake device)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.distributed.pipeline import bubble_fraction

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.distributed.pipeline import pipeline_apply

    from repro.launch.mesh import _make_mesh
    mesh = _make_mesh((4,), ("pipe",))
    rng = np.random.default_rng(0)
    P_, M, B, D, F = 4, 6, 2, 16, 32
    w1 = jnp.asarray(rng.standard_normal((P_, D, F)) * 0.3, jnp.float32)
    w2 = jnp.asarray(rng.standard_normal((P_, F, D)) * 0.3, jnp.float32)
    params = {"w1": w1, "w2": w2}
    x = jnp.asarray(rng.standard_normal((M, B, D)), jnp.float32)

    def stage(p, a):
        return a + jnp.tanh(a @ p["w1"]) @ p["w2"]

    got = jax.jit(lambda p, x: pipeline_apply(stage, p, x, mesh=mesh))(
        params, x)

    ref = x
    for s in range(P_):
        local = jax.tree.map(lambda a: a[s], params)
        ref = jax.vmap(lambda mb: stage(local, mb))(ref)

    err = float(jnp.max(jnp.abs(got - ref)))
    print(json.dumps({"err": err}))
""")


def test_pipeline_matches_sequential():
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", _SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["err"] < 1e-5, res


def test_bubble_fraction():
    assert bubble_fraction(4, 6) == pytest.approx(3 / 9)
    assert bubble_fraction(1, 8) == 0.0
    # more microbatches -> smaller bubble
    assert bubble_fraction(8, 64) < bubble_fraction(8, 8)
