"""Distributed tests on an 8-fake-device mesh (subprocess: device count must
be set before jax initializes, and other tests need the normal 1-device
view). Verifies the sharding rules EXECUTE correctly (not just compile):
sharded train step == single-device train step."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs.base import smoke_config
    from repro.data.pipeline import DataConfig, make_batch
    from repro.distributed import sharding as shd
    from repro.launch.mesh import make_test_mesh
    from repro.models.transformer import init_params, init_cache, decode_step
    from repro.optim.adamw import AdamWConfig, init_opt_state
    from repro.train.step import TrainConfig, make_train_step

    arch = "ARCH"
    cfg = smoke_config(arch)
    mesh = make_test_mesh(data=2, model=2, pod=2)
    rules = shd.Rules(mesh=mesh, data_axes=("pod", "data"))

    params = init_params(cfg, jax.random.PRNGKey(0))
    ocfg = AdamWConfig(lr_peak=1e-3, warmup_steps=1, total_steps=5)
    tcfg = TrainConfig(microbatches=2, optimizer=ocfg)
    opt = init_opt_state(params, ocfg)
    d = DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=8,
                   frontend=cfg.frontend, d_model=cfg.d_model,
                   m_rope=cfg.m_rope)
    batch = make_batch(d, 0)

    # single-device reference
    step = make_train_step(cfg, tcfg)
    p_ref, o_ref, m_ref = jax.jit(step)(params, opt, batch)

    # sharded: place params/opt/batch with the production rules
    pspecs = shd.param_specs(cfg, rules)
    psh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                       is_leaf=lambda s: isinstance(s, P))
    params_s = jax.device_put(params, psh)
    opt_s = jax.device_put(opt, type(opt)(
        step=NamedSharding(mesh, P()), mu=psh, nu=psh))
    bspecs = shd.batch_specs(cfg, rules, "train")
    bsh = {k: NamedSharding(mesh, bspecs[k]) for k in batch}
    batch_s = jax.device_put(batch, bsh)

    def fn(p, o, b):
        with shd.use_rules(rules):
            return step(p, o, b)

    with mesh:
        p_s, o_s, m_s = jax.jit(fn)(params_s, opt_s, batch_s)

    loss_ref = float(m_ref["loss"]); loss_s = float(m_s["loss"])
    maxdiff = max(float(jnp.max(jnp.abs(
        a.astype(jnp.float32) - jax.device_get(b).astype(jnp.float32))))
        for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_s)))

    # decode path on the sharded mesh as well
    cache = init_cache(cfg, 8, 16)
    csp = shd.cache_specs(cfg, rules)
    cache_s = jax.device_put(cache, {k: NamedSharding(mesh, csp[k])
                                     for k in cache})
    if cfg.frontend == "tokens":
        sb = {"tokens": batch["tokens"][:, :1]}
    else:
        sb = {"embeddings": batch["embeddings"][:, :1]}
        if cfg.m_rope:
            sb["positions3"] = batch["positions3"][:, :, :1]
    def dfn(p, b, c):
        with shd.use_rules(rules):
            return decode_step(cfg, p, b, c)
    with mesh:
        lg, _ = jax.jit(dfn)(params_s, jax.device_put(sb), cache_s)
    decode_ok = bool(np.isfinite(np.asarray(lg, np.float32)).all())

    print(json.dumps({"loss_ref": loss_ref, "loss_s": loss_s,
                      "maxdiff": maxdiff, "decode_ok": decode_ok}))
""")


@pytest.mark.parametrize("arch", ["qwen3-14b", "mamba2-370m",
                                  "moonshot-v1-16b-a3b", "zamba2-2.7b"])
def test_sharded_execution_matches_single_device(arch):
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT.replace("ARCH", arch)],
        capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert abs(res["loss_ref"] - res["loss_s"]) < 5e-3, res
    assert res["maxdiff"] < 5e-2, res
    assert res["decode_ok"], res
