"""FLTrainStep host path + the multi-device mesh integration (subprocess
with forged host devices — the ONLY place tests touch a mesh)."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.fl.aggregation import fedavg
from repro.fl.distributed import FLTrainStep, choose_fl_hierarchy
from repro.models import get_model
from repro.optim import sgd


def test_choose_fl_hierarchy_fits():
    for n in (7, 10, 15, 16, 31, 64):
        h = choose_fl_hierarchy(n)
        assert h.min_clients <= n
        assert h.total_clients == n or h.total_clients >= 2


def test_fl_round_host_path_equals_flat_fedavg():
    """mesh=None path: after one round with local_steps=1 and equal
    weights, every client's params equal the flat FedAvg of the locally
    trained replicas."""
    cfg = get_config("stablelm-1.6b").reduced().replace(n_layers=1)
    model = get_model(cfg)
    h = choose_fl_hierarchy(7)
    placement = np.arange(h.dimensions)
    fl = FLTrainStep(model, sgd(0.1), h, placement, local_steps=1)
    round_fn = fl.make_round_fn()

    rng = np.random.default_rng(0)
    params, opt = fl.init_stacked(jax.random.key(0))
    n = fl.n_clients_total
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (n, 2, 8)),
                              jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (n, 2, 8)),
                              jnp.int32),
    }
    new_params, _, metrics = round_fn(params, opt, batch)
    assert bool(jnp.isfinite(metrics["loss"]))

    # reference: train each client separately, flat-average
    opt1 = sgd(0.1)
    updates = []
    for c in range(n):
        p_c = jax.tree.map(lambda x, c=c: x[c], params)
        o_c = opt1.init(p_c)
        b_c = jax.tree.map(lambda x, c=c: x[c], batch)
        (loss, _), g = jax.value_and_grad(model.loss_fn, has_aux=True)(p_c, b_c)
        p_c, _ = opt1.update(p_c, g, o_c)
        updates.append(p_c)
    flat = fedavg(updates, [1.0 / n] * n)
    for a, b in zip(jax.tree.leaves(flat),
                    jax.tree.leaves(jax.tree.map(lambda x: x[0], new_params)),
                    strict=True):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=3e-4, atol=3e-5)


MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import numpy as np
    import jax, jax.numpy as jnp

    from repro.configs import get_config
    from repro.fl.distributed import FLTrainStep
    from repro.core.hierarchy import Hierarchy
    from repro.fl.aggregation import fedavg
    from repro.models import get_model
    from repro.models.sharding import ShardingPolicy
    from repro.optim import sgd
    from repro.launch.mesh import make_mesh
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = make_mesh((4, 2), ("data", "model"))
    cfg = get_config("stablelm-1.6b").reduced().replace(n_layers=1)
    policy = ShardingPolicy(mesh=mesh, batch_axes=None, model_axis="model")
    model = get_model(cfg, policy)
    h = Hierarchy(depth=2, width=1, trainers_per_leaf=2, n_clients=4)
    fl = FLTrainStep(model, sgd(0.1), h, np.arange(h.dimensions),
                     local_steps=1, mode="hierarchical")
    round_fn = fl.make_round_fn()
    n = fl.n_clients_total
    rng = np.random.default_rng(0)
    params, opt = fl.init_stacked(jax.random.key(0))
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (n, 2, 8)), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (n, 2, 8)), jnp.int32),
    }
    specs = fl.stacked_param_pspecs()
    ns = lambda tree: jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                                   is_leaf=lambda s: isinstance(s, P))
    jitted = jax.jit(round_fn)
    new_params, _, metrics = jitted(
        jax.device_put(params, ns(specs)), opt, batch)

    # reference: per-client local step + flat fedavg on host
    opt1 = sgd(0.1)
    updates = []
    for c in range(n):
        p_c = jax.tree.map(lambda x, c=c: np.asarray(x[c]), params)
        b_c = jax.tree.map(lambda x, c=c: x[c], batch)
        (l, _), g = jax.value_and_grad(model.loss_fn, has_aux=True)(p_c, b_c)
        p_c, _ = opt1.update(p_c, g, opt1.init(p_c))
        updates.append(p_c)
    flat = fedavg(updates, [1.0 / n] * n)
    errs = []
    got0 = jax.tree.map(lambda x: np.asarray(x[0], np.float32), new_params)
    for a, b in zip(jax.tree.leaves(flat), jax.tree.leaves(got0),
                    strict=True):
        errs.append(float(np.max(np.abs(np.asarray(a, np.float32) - b))))
    print(json.dumps({"max_err": max(errs), "loss": float(metrics["loss"])}))
""")


def test_hierarchical_psum_on_8_device_mesh():
    """End-to-end numeric check of the grouped-psum aggregation on a real
    (forged) 4x2 device mesh, vs host flat FedAvg."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", MESH_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["max_err"] < 5e-4, res
    assert np.isfinite(res["loss"])
