"""Numeric equivalence of the 2-D expert serving layout (§Perf it.3):
the ep2d path must produce the same outputs as the unsharded dense
dispatch — sharding moves bytes, never math. Runs on a forged 4x2
device mesh in a subprocess (tests otherwise keep the 1-device world)."""
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
    import dataclasses

    from repro.configs import MoEConfig
    from repro.models.moe import init_moe, moe_ffn
    from repro.models.sharding import ShardingPolicy, UNSHARDED

    from repro.launch.mesh import make_mesh

    mesh = make_mesh((4, 2), ("data", "model"))
    cfg = MoEConfig(n_experts=8, top_k=2, d_ff_expert=64)
    d = 32
    params = init_moe(jax.random.key(0), d, cfg, jnp.float32)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((4, 16, d)) * 0.3, jnp.float32)

    ref, aux_ref = moe_ffn(params, x, cfg, UNSHARDED)

    pol = ShardingPolicy(mesh=mesh, batch_axes=("data",),
                         model_axis="model", ep2d_axis="data")
    out, aux = jax.jit(lambda p, x: moe_ffn(p, x, cfg, pol))(params, x)

    err = float(jnp.max(jnp.abs(out - ref)))
    print(json.dumps({"max_err": err,
                      "aux_err": float(abs(aux - aux_ref))}))
""")


def test_ep2d_matches_unsharded():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["max_err"] < 2e-4, res
    assert res["aux_err"] < 1e-5, res
