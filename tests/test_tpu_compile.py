"""The main path compiled for a described TPU v5e — no chip attached.

JAX's TPU compiler is installed with JAX and compiles for a chip that
is described (``jax.experimental.topologies``), which catches what the
Pallas interpreter and the CPU backend cannot: block tilings, gathers
the TPU lowering refuses, VMEM overruns, programs that do not fit.
Compiled at real sizes:

* the Pallas TPD kernel at the ``paper-fig3`` and ``large-10k`` shapes,
  and the scoring program that builds its leaf loads on the device;
* the batched round engine's ``local_all`` step for ``paper-mlp-1m8``
  at the ``paper-fig4`` cohort of 10 clients;
* the sharded float64 pooled TPD evaluator on a mesh of the 4 described
  devices of one v5e host;
* DeepSeek-V2-Lite's held-expert layer, forward and backward, at its
  published widths: the megablox grouped matmul (``gmm``/``tgmm``).

Nothing runs, so nothing here says anything about results or times:
``chip_smoke.py`` checks those on the chip. The topology is described
inside a fixture, never at import: only one process at a time may load
the TPU library.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.core.cost_model import PooledTPDEvaluator
from repro.experiments import get_scenario
from repro.kernels.tpd import batch_tpd_pallas, tpd_scores
from repro.launch.mesh import make_mesh


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    cache_was_on = jax.config.jax_enable_compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here: nothing to test
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be cached but never read back
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", cache_was_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                       sharding=sharding), tree)


@pytest.mark.parametrize("scenario,n_particles,penalty", [
    ("paper-fig3", 20, 0.0),
    ("large-10k", 20, 0.0),
    ("large-10k", 256, 3.0),   # two lane tiles, memcap penalty on
])
def test_tpd_kernel_compiles_for_v5e(one_chip, scenario, n_particles,
                                     penalty):
    h = get_scenario(scenario).make_hierarchy()
    args = (jax.ShapeDtypeStruct((n_particles, h.dimensions), jnp.int32,
                                 sharding=one_chip),
            jax.ShapeDtypeStruct((3, h.total_clients), jnp.float32,
                                 sharding=one_chip),
            jax.ShapeDtypeStruct((n_particles, h.n_leaves), jnp.float32,
                                 sharding=one_chip))
    compiled = batch_tpd_pallas.lower(
        *args, depth=h.depth, width=h.width, penalty=penalty,
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 64 * 2 ** 20


@pytest.mark.parametrize("n_particles,penalty", [(83, 0.0), (256, 3.0)])
def test_tpd_scoring_program_compiles_for_v5e(one_chip, n_particles,
                                              penalty):
    """The search's scoring program at ``large-10k``: the leaf loads
    built on the device and the kernel, one program whose only
    arguments are the placements and the attribute table."""
    h = get_scenario("large-10k").make_hierarchy()
    args = (jax.ShapeDtypeStruct((n_particles, h.dimensions), jnp.int32,
                                 sharding=one_chip),
            jax.ShapeDtypeStruct((3, h.total_clients), jnp.float32,
                                 sharding=one_chip))
    compiled = tpd_scores.lower(*args, depth=h.depth, width=h.width,
                                penalty=penalty, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    # no (P, C) operand: not a byte per placement-client pair comes in
    assert mem.argument_size_in_bytes < n_particles * h.total_clients
    assert mem.temp_size_in_bytes < 64 * 2 ** 20


def test_round_engine_local_step_compiles_for_v5e(one_chip):
    spec = get_scenario("paper-fig4")
    assert spec.model == "paper-mlp-1m8"
    orch = spec.make_environment(0).orchestrator
    [(ids, idx)] = orch._collect_batches(0)
    assert len(ids) == 10
    # the indexed step: batches gathered from the device-resident set
    local_all = orch._local_fn_for(True)
    compiled = local_all.lower(_sds(orch.params, one_chip),
                               *_sds(orch._device_samples(), one_chip),
                               _sds(idx, one_chip)).compile()
    n_params = sum(x.size for x in jax.tree.leaves(orch.params))
    assert n_params > 1_700_000
    # the sample set is an argument of the program, not a constant in it
    assert compiled.memory_analysis().argument_size_in_bytes >= \
        orch.data.base.features.nbytes
    # 10 stacked f32 copies of the parameters come back
    assert compiled.memory_analysis().output_size_in_bytes >= \
        10 * 4 * n_params


def test_sharded_pooled_evaluator_compiles_for_v5e_2x2(topo):
    spec = get_scenario("large-10k")
    models = [spec.make_environment(s).cost_model for s in range(4)]
    h = models[0].hierarchy
    mesh = make_mesh((4,), ("rows",), devices=topo.devices)
    rows = NamedSharding(mesh, PartitionSpec("rows"))
    n_rows = 256
    with jax.enable_x64(True):
        fn = PooledTPDEvaluator(models).sharded_fn(mesh, n_rows)
        compiled = jax.jit(fn).lower(
            jax.ShapeDtypeStruct((n_rows, h.dimensions), jnp.int32,
                                 sharding=rows),
            jax.ShapeDtypeStruct((n_rows,), jnp.int64,
                                 sharding=rows)).compile()
    hlo = compiled.as_text()
    # the psum merge of the row segments crosses the 4 chips (the TPU
    # compiler may turn the psum of disjoint segments into an all-gather)
    assert "all-reduce" in hlo or "all-gather" in hlo
    assert compiled.memory_analysis() is not None


def test_held_expert_layer_compiles_for_v5e(one_chip, monkeypatch):
    """One DeepSeekMoE layer's held experts (8 of 64, top-6 of 4,096
    tokens, D 2,048, F 1,408) through the grouped matmul kernel and its
    backward; the layer takes the kernel only where the backend is a
    TPU, so the test says it is."""
    import dataclasses

    from repro.configs import get_config
    from repro.models.moe import held_experts
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(get_config("deepseek-v2-lite").moe, n_held=8)
    t, d, f = 4096, 2048, cfg.d_ff_expert
    params = {"w_gate": jax.ShapeDtypeStruct((8, d, f), jnp.float32,
                                             sharding=one_chip),
              "w_up": jax.ShapeDtypeStruct((8, d, f), jnp.float32,
                                           sharding=one_chip),
              "w_down": jax.ShapeDtypeStruct((8, f, d), jnp.float32,
                                             sharding=one_chip)}

    def loss(p, x, w, i):
        out, pairs = held_experts(x, w, i, p, cfg)
        return jnp.sum(out), pairs

    args = (jax.ShapeDtypeStruct((t, d), jnp.bfloat16, sharding=one_chip),
            jax.ShapeDtypeStruct((t, cfg.top_k), jnp.float32,
                                 sharding=one_chip),
            jax.ShapeDtypeStruct((t, cfg.top_k), jnp.int32,
                                 sharding=one_chip))
    compiled = jax.jit(jax.grad(loss, has_aux=True)).lower(
        params, *args).compile()
    kernels = [line.split(" = ", 1)[0] for line in
               compiled.as_text().splitlines() if "tpu_custom_call" in line]
    # the names the trace reduction finds them by (``round.expert_roofline``)
    assert kernels and all("gmm" in k for k in kernels)
    assert any("tgmm" in k for k in kernels)
    # no capacity buffer per expert: the rows are the T x k pairs
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2 ** 30
