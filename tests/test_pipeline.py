"""Pipeline parallelism: GPipe over the pod axis must be numerically
identical (loss AND grads) to the unpipelined model. Forged (2, 2, 2)
pod/data/model mesh in a subprocess: partial-auto ``jax.shard_map``,
manual over the pod axis only."""
import json
import os
import subprocess
import sys
import textwrap


SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import numpy as np
    import jax, jax.numpy as jnp

    from repro.configs import get_config
    from repro.models import get_model
    from repro.models.pipeline import make_pp_loss_fn
    from repro.models.sharding import ShardingPolicy
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    cfg = get_config("stablelm-1.6b").reduced().replace(
        n_layers=2, remat=False, dtype="float32")  # f32: exact comparison
    policy = ShardingPolicy(mesh=mesh)  # unsharded inside stages (tiny)
    model = get_model(cfg)              # reference: UNSHARDED build
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 16)),
                              jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 16)),
                              jnp.int32),
    }
    ref_loss, _ = model.loss_fn(params, batch)
    ref_grads = jax.grad(lambda p: model.loss_fn(p, batch)[0])(params)

    pp_loss_fn = make_pp_loss_fn(cfg, policy, mesh, n_micro=2)
    pp_loss, _ = jax.jit(pp_loss_fn)(params, batch)
    pp_grads = jax.jit(jax.grad(lambda p: pp_loss_fn(p, batch)[0]))(params)

    gerr = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) -
                                     b.astype(jnp.float32))))
               for a, b in zip(jax.tree.leaves(ref_grads),
                               jax.tree.leaves(pp_grads), strict=True))
    print(json.dumps({
        "loss_err": abs(float(pp_loss) - float(ref_loss)),
        "grad_err": gerr,
        "ref_loss": float(ref_loss),
    }))
""")


def test_pipeline_matches_unpipelined():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["loss_err"] < 1e-4, res
    assert res["grad_err"] < 1e-3, res
