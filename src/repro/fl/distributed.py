"""The paper's technique as a first-class distributed training step.

Mapping SDFL onto a TPU mesh (see DESIGN.md):

* Every FL **client owns a slice of the data axis** and holds its *own*
  replica of the model: parameters carry a leading client dim ``C``
  sharded over ``('pod',) + ('data',)``. Local training is a ``vmap``
  over that dim — embarrassingly parallel, ZERO cross-client collectives
  (GSPMD keeps tensor-parallel ``model``-axis math inside each client).
* One FL round = ``local_steps`` local updates followed by
  **hierarchical aggregation along the placement tree**: a partial-manual
  ``shard_map`` (manual over pod/data, auto over model) running one
  grouped ``psum`` per tree level (``aggregation.hierarchical_psum``).
  The placement decides the groups; the roofline's collective term sees
  exactly the schedule the paper optimizes.
* The flat baseline (CFL) is the same round with a single ungrouped
  psum.

Multi-pod: each pod hosts its own client set (same per-pod placement);
the tree's root level is a ``pmean`` across the ``pod`` axis — the
hierarchy's top level aligned with the DCN boundary.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.hierarchy import Hierarchy
from repro.fl.aggregation import AggregationPlan, flat_psum, hierarchical_psum
from repro.models.api import Model


class FLTrainStep:
    """Builder for the federated round step of any zoo ``Model``.

    Produces pure functions over *client-stacked* pytrees: every param
    leaf gets a leading ``n_clients_total`` dim (n_pods * clients_per_pod)
    sharded over the pod+data axes.
    """

    def __init__(self, model: Model, optimizer, hierarchy: Hierarchy,
                 placement: Sequence[int], *,
                 weights: Optional[Sequence[float]] = None,
                 local_steps: int = 1, mode: str = "hierarchical"):
        self.model = model
        self.optimizer = optimizer
        self.hierarchy = hierarchy
        self.placement = np.asarray(placement, np.int64)
        self.local_steps = local_steps
        self.mode = mode
        policy = model.policy
        self.mesh = policy.mesh
        if self.mesh is not None:
            self.n_pods = self.mesh.shape.get("pod", 1)
            self.data_size = self.mesh.shape.get("data", 1)
        else:
            self.n_pods = 1
            self.data_size = hierarchy.total_clients  # host path: 1 dev/client
        self.clients_per_pod = hierarchy.total_clients
        self.n_clients_total = self.clients_per_pod * self.n_pods
        self.plan = AggregationPlan.build(
            hierarchy, self.placement, self.data_size, weights)

    # ------------------------------------------------------------------
    @property
    def client_axes(self):
        if self.mesh is None:
            return None
        axes = tuple(a for a in ("pod", "data") if a in self.mesh.axis_names)
        return axes if axes else None

    def stacked_param_pspecs(self):
        """Per-leaf specs: leading client dim over (pod, data); remaining
        dims keep the model-axis sharding from the model's spec_rule
        (fsdp resolves to None — client replicas exclude data-axis FSDP)."""
        base = self.model.param_pspecs()
        c = self.client_axes

        def stackspec(spec):
            parts = [c]
            for s in spec:
                # drop data/pod axes from param dims (used by client dim)
                if s in ("data", "pod") or (isinstance(s, tuple) and
                                            any(a in ("data", "pod") for a in s)):
                    parts.append(None)
                else:
                    parts.append(s)
            return P(*parts)

        return jax.tree.map(stackspec, base,
                            is_leaf=lambda s: isinstance(s, P))

    def init_stacked(self, rng):
        """Stacked params + opt state (all clients start from one init)."""
        params = self.model.init(rng)
        opt_state = self.optimizer.init(params)
        n = self.n_clients_total

        def stack(x):
            return jnp.broadcast_to(x, (n,) + x.shape)

        return (jax.tree.map(stack, params), jax.tree.map(stack, opt_state))

    # ------------------------------------------------------------------
    def make_round_fn(self):
        """(params_stacked, opt_stacked, batch_stacked) ->
        (params_stacked, opt_stacked, metrics).

        batch_stacked leaves: (n_clients_total, per_client_batch, ...).
        """
        model, optimizer = self.model, self.optimizer
        local_steps = self.local_steps
        plan, mode = self.plan, self.mode
        mesh = self.mesh
        pod_axis = "pod" if (mesh is not None and "pod" in mesh.axis_names) \
            else None

        def local_round(params, opt_state, batch):
            def one_step(carry, _):
                params, opt_state = carry
                (loss, _), grads = jax.value_and_grad(
                    model.loss_fn, has_aux=True)(params, batch)
                params, opt_state = optimizer.update(params, grads, opt_state)
                return (params, opt_state), loss

            (params, opt_state), losses = jax.lax.scan(
                one_step, (params, opt_state), None, length=local_steps)
            return params, opt_state, losses[-1]

        def aggregate(params_stacked):
            if mesh is None:
                # host path (tests): tree-equivalent weighted FedAvg
                # (plan built with 1 device per client => weight_of_device
                # is exactly the per-client weight)
                from repro.utils.trees import tree_weighted_sum
                updates = [jax.tree.map(lambda x, i=i: x[i], params_stacked)
                           for i in range(self.n_clients_total)]
                glob = tree_weighted_sum(updates,
                                         list(plan.weight_of_device))
                return jax.tree.map(
                    lambda g: jnp.broadcast_to(
                        g, (self.n_clients_total,) + g.shape), glob)

            def agg_body(tree):
                # local view: client dim is size 1 on each device slice
                squeezed = jax.tree.map(lambda x: x[0], tree)
                if mode == "hierarchical":
                    out = hierarchical_psum(squeezed, plan, "data", pod_axis)
                else:
                    out = flat_psum(squeezed, plan, "data", pod_axis)
                return jax.tree.map(lambda x: x[None], out)

            # Full-manual over every mesh axis: the body is elementwise
            # (grouped psums over pod/data), so model-axis shards pass
            # through untouched. Partial-auto shard_map would also work
            # on current JAX, but on 0.4.x it lowers axis_index to a
            # PartitionId op the CPU SPMD partitioner rejects.
            specs = self.stacked_param_pspecs()
            return jax.shard_map(
                agg_body, mesh=mesh,
                in_specs=(specs,), out_specs=specs,
                axis_names=set(mesh.axis_names), check_vma=False,
            )(params_stacked)

        def round_fn(params_stacked, opt_stacked, batch_stacked):
            # spmd_axis_name tells GSPMD the client dim's mesh axes so
            # sharding constraints inside local_round (e.g. the
            # sequence-parallel hints) batch correctly
            spmd = self.client_axes if mesh is not None else None
            vmapped = jax.vmap(local_round, spmd_axis_name=spmd)
            params_stacked, opt_stacked, losses = vmapped(
                params_stacked, opt_stacked, batch_stacked)
            if mode != "none":
                params_stacked = aggregate(params_stacked)
            return params_stacked, opt_stacked, {"loss": jnp.mean(losses)}

        return round_fn

    # ------------------------------------------------------------------
    # repro-lint: disable=RPL001 (shape helper, no vectorized compute to pin)
    def batch_shape(self, shape_cfg) -> dict:
        """Per-client batch split of a global shape."""
        per = shape_cfg.global_batch // self.n_clients_total
        return {"per_client_batch": max(per, 1),
                "n_clients": self.n_clients_total}


# the historical preference ladder (deeper trees first) and, above it,
# the swarm-scale rungs the elastic environments opt into
_BASE_LADDER = ((3, 2, 2), (3, 2, 1), (2, 3, 4), (2, 3, 3),
                (2, 2, 4), (2, 2, 2), (2, 2, 1))
_SCALE_LADDER = ((6, 4, 2), (6, 3, 2), (5, 3, 2), (4, 3, 2),
                 (4, 2, 2)) + _BASE_LADDER


def choose_fl_hierarchy(n_clients: int, *, scale: bool = False) -> Hierarchy:
    """Pick a depth/width whose minimum client count fits ``n_clients``.

    Preference order: deeper trees first (more interesting schedules).
    Extra clients beyond the minimum become additional trainers (the
    round-robin assignment absorbs them).

    ``scale=True`` extends the ladder with the swarm-scale rungs
    (depth-4 .. depth-6, the large-1k/large-10k tree shapes) so a large
    population keeps a proportionate tree instead of collapsing onto
    the 7-slot depth-3 one — this is what the elastic environments use
    to re-hierarchize a GROWING population (a flash crowd climbs
    depth-2 -> -3 -> -4 as it crosses each rung's minimum). The default
    keeps the historical small-cluster ladder, so launch/bench/example
    callers build the same trees as before.
    """
    for depth, width, tpl in (_SCALE_LADDER if scale else _BASE_LADDER):
        if Hierarchy(depth, width, tpl).min_clients <= n_clients:
            return Hierarchy(depth=depth, width=width, trainers_per_leaf=tpl,
                             n_clients=n_clients)
    return Hierarchy(depth=1, width=1, trainers_per_leaf=1,
                     n_clients=max(n_clients, 2))


def elastic_rehierarchize(old: Hierarchy, n_clients: int,
                          capacity: int) -> tuple:
    """THE capacity-window re-hierarchization rule of the elastic tracks.

    Returns ``(new_hierarchy, new_capacity)`` for a population that just
    resized to ``n_clients`` under a tree previously allowed to carry up
    to ``capacity`` clients. Outside the window ``[old.min_clients,
    capacity]`` the structure is rebuilt through
    :func:`choose_fl_hierarchy` (scale ladder) and the capacity re-pins
    to the new tree's bound; inside it, the same tree shape is kept and
    only ``n_clients`` is re-pinned (cheaper migration, identity
    ``slot_remap``). Deterministic — no rng is consumed — and shared by
    ``SimulatedEnvironment.sync_topology`` and
    ``FederatedOrchestrator.sync_population`` so the two tracks replay
    the SAME hierarchy sequence for the same event schedule (the
    emulated-vs-simulated elastic parity tests pin this).
    """
    if n_clients < old.min_clients or n_clients > capacity:
        new = choose_fl_hierarchy(n_clients, scale=True)
        return new, max(new.max_clients, n_clients)
    return Hierarchy(depth=old.depth, width=old.width,
                     trainers_per_leaf=old.trainers_per_leaf,
                     n_clients=n_clients), capacity


def shard_rows(fn, mesh, n_rows: int, axis: str = "rows"):
    """Row-shard a batched evaluator across ``mesh[axis]`` devices.

    ``fn`` maps per-row inputs ``(rows, ...)`` to per-row outputs
    ``(rows,)``. The returned callable splits every input along axis 0
    into per-device shards under ``shard_map`` (full-manual — partial-
    auto does not lower on legacy CPU backends), runs ``fn`` on each
    shard, and merges with the segment-sum trick the aggregation plans
    use: each device scatters its shard into the zeros of the full
    (n_rows,) output at its global row offsets and one ``psum`` across
    the axis adds the disjoint segments back together.

    ``n_rows`` not divisible by the axis size is handled by padding
    with copies of row 0 (computed and discarded — every device keeps
    an identical shard shape, which shard_map requires).
    """
    ndev = mesh.shape[axis]
    pad = (-n_rows) % ndev
    total = n_rows + pad
    shard = total // ndev

    def body(*local):
        vals = fn(*local)                               # (shard,)
        idx = jax.lax.axis_index(axis) * shard + jnp.arange(shard)
        seg = jax.ops.segment_sum(vals, idx, num_segments=total)
        return jax.lax.psum(seg, axis)

    sharded = jax.shard_map(
        body, mesh=mesh,
        in_specs=P(axis), out_specs=P(),
        axis_names={axis}, check_vma=False)

    def run(*arrays):
        if pad:
            arrays = tuple(
                jnp.concatenate(
                    [a, jnp.broadcast_to(a[:1], (pad,) + a.shape[1:])])
                for a in map(jnp.asarray, arrays))
        return sharded(*arrays)[:n_rows]

    return run
