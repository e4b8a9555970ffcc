"""The TPD cost model (paper eqs. 6-7) — scalar and particle-vectorized.

    d_a = (mdatasize_a + sum_{c in children(a)} mdatasize_c) / pspeed_a
    TPD = sum_levels max_{a in level} d_a

The max-per-level captures the bottleneck effect (aggregators at one
level run in parallel; levels are serial, bottom-up). An optional memory
penalty inflates d_a when the buffer exceeds the host's memcap — the
"compute memory consumption" line of Algorithm 1.

Evaluator tiers (all built from ONE closure, ``_make_batch_tpd``):

* ``tpd`` — the scalar Python reference (paper-literal; the oracle every
  vectorized path is parity-pinned against).
* ``tpd_fast`` — single-placement hot path: the cached EXACT (float64
  numpy) vectorized evaluator on a batch of 1. Bit-identical to ``tpd``
  for trees with width < 8 (numpy sums small axes sequentially, matching
  the scalar left-to-right accumulation; at width >= 8 numpy switches to
  unrolled partial sums and agreement drops to ~1e-15 relative).
* ``batch_tpd`` — whole-swarm (P, D) -> (P,) evaluation; numpy fast path
  below ``_NP_FASTPATH_ELEMS``; above it, on TPU, one device program
  that builds the trainer-split leaf loads and runs the Pallas kernel
  (``repro.kernels.tpd.tpd_scores``), jit'd XLA on every other backend.
* ``PooledTPDEvaluator`` — S same-shape cost models with independent
  client pools evaluated in ONE exact call (the batched sweep runner's
  engine: placement row i scores against pool ``pool_idx[i]``).

Cache invalidation is O(1): evaluators are keyed on the ClientPool's
mutation ``version`` counter (see ``repro.core.hierarchy.ClientPool``),
not on hashing the attribute arrays.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.hierarchy import ClientPool, Hierarchy, rows_with_duplicates
from repro.utils import tracing


@dataclass(frozen=True)
class CostModel:
    hierarchy: Hierarchy
    clients: ClientPool
    memory_penalty: float = 0.0  # 0 disables the memcap feasibility term

    # ------------------------------------------------------------------
    def cluster_delay(self, host: int, children: Sequence[int]) -> float:
        """Paper eq. 6 (+ optional memcap penalty)."""
        mds = self.clients.mdatasize
        load = mds[host] + sum(mds[c] for c in children)
        delay = load / self.clients.pspeed[host]
        if self.memory_penalty > 0:
            over = max(0.0, load - self.clients.memcap[host])
            delay *= 1.0 + self.memory_penalty * over / max(
                self.clients.memcap[host], 1e-9)
        return float(delay)

    def tpd(self, placement: Sequence[int]) -> float:
        """Paper eq. 7: bottom-up BFT, sum of per-level maxima.

        Scalar reference — O(total_clients) of Python-level work per
        call; every hot path rides ``tpd_fast``/``batch_tpd`` instead,
        and the parity suite pins them to this implementation.
        """
        h = self.hierarchy
        children = h.children_clients(placement)
        total = 0.0
        for level in range(h.depth - 1, -1, -1):
            worst = 0.0
            for s in range(h.level_starts[level], h.level_starts[level + 1]):
                worst = max(worst,
                            self.cluster_delay(int(placement[s]), children[s]))
            total += worst
        return total

    def fitness(self, placement: Sequence[int]) -> float:
        """Paper eq. 1: f = -T."""
        return -self.tpd(placement)

    # ------------------------------------------------------------------
    # vectorized path (all particles at once, jit'd)
    # ------------------------------------------------------------------
    # a 10-particle swarm over a few hundred clients is a handful of
    # sub-microsecond array ops; below this many placement entries the
    # numpy evaluator beats the jit'd one (per-op XLA-CPU overhead)
    _NP_FASTPATH_ELEMS = 32768

    def _attr_stack(self, dtype) -> np.ndarray:
        """Stacked (A, C) client-attribute table: mdatasize, pspeed,
        memcap(, pod id) — ONE fancy-index gathers every per-host
        attribute (numpy per-op dispatch is the floor here)."""
        rows = [self.clients.mdatasize, self.clients.pspeed,
                self.clients.memcap]
        pod = getattr(self, "pod_of", None)
        if pod is not None:
            rows.append(np.asarray(pod))  # pod ids exact in f32
        return np.stack(rows).astype(dtype)

    def _make_batch_tpd(self, xp=None, dtype=None, pool_attrs=None):
        """Build the (P, slots) -> (P,) TPD evaluator over namespace
        ``xp`` (numpy or jax.numpy; the jax build is jit'd).

        Mirrors the scalar path exactly: the canonical round-robin
        trainer split is recomputed per particle (rank of each unplaced
        client in ascending id order, mod n_leaves), so heterogeneous
        ``mdatasize`` charges the ACTUAL per-child loads — not a mean —
        and subclasses can layer per-edge costs (``pod_of`` + ICI/DCN
        rates, the TwoTier model) on true child identities.

        ``dtype`` is the accumulation dtype (default float32). The
        float64 numpy build is the EXACT path: every reduction runs in
        the same order as the scalar reference (bincount/left-to-right
        child sums, division by pspeed, per-level maxima summed deepest
        level first), so it is bit-identical to ``tpd`` for width < 8.

        ``pool_attrs`` switches on POOLED mode: a (A, S, C) stack of S
        client pools' attribute tables; the returned evaluator takes
        ``(placements, pool_idx=None)`` and scores placement row i
        against pool ``pool_idx[i]`` (default: row i against pool i,
        requiring P == S). Row results are bit-identical to the
        single-pool evaluator of the matching pool — all per-row
        reductions are independent.
        """
        h = self.hierarchy
        C, D, depth = h.total_clients, h.dimensions, h.depth
        n_leaves = h.n_leaves
        leaf_start = h.level_starts[depth - 1]
        kids_np = h.kids_table
        penalty = self.memory_penalty
        have_pods = getattr(self, "pod_of", None) is not None
        ici = float(getattr(self, "ici_cost", 0.0))
        dcn = float(getattr(self, "dcn_cost", 0.0))
        # trace-calibrated terms (CalibratedCostModel; neutral values on
        # the base model keep every branch below bit-identical to the
        # uncalibrated build)
        cal_scale, cal_link, cal_train = self._calibration_terms()
        calibrated = (cal_scale != 1.0 or any(cal_link)
                      or cal_train != 0.0)
        link_np = np.zeros(D, np.float64)
        if calibrated and cal_link:
            link = np.asarray(cal_link, np.float64)
            link_np = link[np.minimum(h.levels, len(link) - 1)]
        kids_cnt_np = (kids_np >= 0).sum(axis=1)                  # (D,)
        tr_counts_np = np.bincount(np.arange(max(C - D, 0)) % n_leaves,
                                   minlength=n_leaves)
        # level boundaries are static: per-level max is a sliced reduce
        # (scatter/segment ops are 50x slower than dense math on CPU XLA,
        # so the whole evaluator is dense: one-hot einsums, no scatter)
        level_bounds = [(h.level_starts[lv], h.level_starts[lv + 1])
                        for lv in range(depth)]

        if xp is None:
            xp = jnp
        if xp is jnp:
            def bincount(idx, w, m):
                return jnp.bincount(
                    idx.ravel(),
                    weights=None if w is None else w.ravel(), length=m)
        else:
            def bincount(idx, w, m):
                return np.bincount(
                    idx.ravel(),
                    weights=None if w is None else w.ravel(),
                    minlength=m)
        ft = np.dtype(dtype if dtype is not None else np.float32).type
        pooled = pool_attrs is not None
        if pooled:
            attrs_np = np.asarray(pool_attrs)               # (A, S, C)
        else:
            attrs_np = self._attr_stack(ft)                 # (A, C)
        # uniform-payload fast path: when every client's mdatasize is
        # equal (the paper's Sec. IV-A default pools) the canonical
        # trainer split fixes each leaf cluster's LOAD, not just its
        # size, so the whole per-call (P, C) rank/scatter pipeline
        # collapses to a per-slot constant — bit-identical because the
        # constants are accumulated by the same repeated addition the
        # bincount would perform. The constants assume exactly C - D
        # distinct placed ids, so rows with DUPLICATE ids (legal for the
        # scalar model) take the general path — a per-call runtime
        # check, which is why this is numpy-only (the branch cannot
        # trace under jit).
        mds_rows = attrs_np[0][None] if not pooled else attrs_np[0]
        uniform = xp is np and not have_pods and all(
            row.size and np.all(row == row[0]) for row in mds_rows)
        if uniform:
            counts = np.bincount(np.arange(max(C - D, 0)) % n_leaves,
                                 minlength=n_leaves)

            def leaf_consts(u):
                # cumsum of a constant == the bincount's sequential
                # repeated addition, prefix by prefix (bit-identical)
                kmax = int(counts.max()) if counts.size else 0
                acc = np.concatenate(
                    [[np.float64(0.0)],
                     np.cumsum(np.full(kmax, u, np.float64))])
                return acc[counts]

            leaf_part_np = np.zeros((mds_rows.shape[0], D), np.float64)
            leaf_part_np[:, leaf_start:] = np.stack(
                [leaf_consts(np.float64(row[0])) for row in mds_rows])
            leaf_part = xp.asarray(leaf_part_np.astype(ft))  # (S|1, D)
        # gather only the attribute rows each site consumes: hosts need
        # mds+pspeed (+memcap when the penalty is live, +pod for two-
        # tier); children only their mds (+pod) — halves gather volume
        host_rows = [0, 1] + ([2] if penalty > 0 else []) + \
            ([3] if have_pods else [])
        kid_rows = [0] + ([3] if have_pods else [])
        h_attrs = xp.asarray(attrs_np[host_rows])
        k_attrs = xp.asarray(attrs_np[kid_rows])
        mds_all = xp.asarray(attrs_np[0])          # (C,) | (S, C)
        pods_all = xp.asarray(attrs_np[3]) if have_pods else None
        kids = xp.asarray(np.clip(kids_np, 0, D - 1))
        kids_valid = xp.asarray(kids_np >= 0)
        is_leaf_slot = xp.asarray(h.levels == depth - 1)
        slot_leaf_idx = xp.clip(xp.arange(D) - leaf_start, 0, n_leaves - 1)
        level_starts_np = np.asarray(h.level_starts[:-1], np.int32)
        # calibrated-link statics: per-slot beta (level gather) and the
        # structural member count of every cluster for NON-duplicate
        # rows (kids + host for internal slots, round-robin trainers +
        # host for leaves); duplicate rows recount trainers per call
        link_slot = xp.asarray(link_np.astype(ft))
        kid_parts = xp.asarray((kids_cnt_np + 1).astype(ft))
        slot_leaf_np = np.clip(np.arange(D) - leaf_start, 0, n_leaves - 1)
        static_parts = xp.asarray(np.where(
            h.levels == depth - 1, tr_counts_np[slot_leaf_np] + 1,
            kids_cnt_np + 1).astype(ft))
        train_add = None
        if calibrated and cal_train != 0.0:
            psp = attrs_np[1]
            inv_max = np.max(1.0 / psp, axis=-1)   # () | (S,)
            if pooled:
                train_add = xp.asarray(
                    (cal_train * inv_max).astype(ft))
            else:
                train_add = ft(cal_train * inv_max)
        iota_cache = {}

        def iota(P):
            if xp is not np:       # never cache tracers across jit traces
                return xp.arange(P)
            got = iota_cache.get(P)
            if got is None:
                got = iota_cache[P] = np.arange(P)
            return got

        def batch(placements, pool_idx=None):           # (P, D) int
            placements = placements.astype(np.int32)
            P = placements.shape[0]
            rows = iota(P) if pool_idx is None else xp.asarray(pool_idx)
            use_uniform = uniform and \
                not rows_with_duplicates(placements).any()
            if not use_uniform:
                p_off = iota(P)[:, None]
                # placed mask via bincount, not a (P, D, C) compare
                placed = bincount(placements + C * p_off, None,
                                  P * C).reshape(P, C)
                unplaced = placed == 0
                mds_b = mds_all[rows] if pooled else mds_all[None]
                t_mds = xp.where(unplaced, mds_b, ft(0.0))
                # canonical trainer split: rank among unplaced ids, mod
                # leaves
                leaf_of = (xp.cumsum(unplaced, axis=1) - 1) % n_leaves
                leaf_bins = leaf_of + n_leaves * p_off
            if pooled:
                host = h_attrs[:, rows[:, None], placements]  # (Ah,P,D)
            else:
                host = h_attrs[:, placements]                 # (Ah,P,D)

            kid_host = placements[:, kids]                   # (P, D, W)
            if pooled:
                kid_attr = k_attrs[:, rows[:, None, None], kid_host]
            else:
                kid_attr = k_attrs[:, kid_host]              # (Ak,P,D,W)
            kid_mds = xp.where(kids_valid[None], kid_attr[0], ft(0.0))

            if have_pods:  # TwoTier per-edge transfer costs
                host_pod = host[-1]                          # (P, D)
                kid_rate = xp.where(kid_attr[-1] == host_pod[:, :, None],
                                    ft(ici), ft(dcn))
                edge_int = xp.sum(
                    xp.where(kids_valid[None], kid_mds * kid_rate,
                             ft(0.0)), axis=2)
                t_host_pod = host_pod.reshape(-1)[
                    (leaf_start + leaf_of) + D * p_off]      # (P, C)
                pods_b = pods_all[rows] if pooled else pods_all[None]
                t_rate = xp.where(pods_b == t_host_pod,
                                  ft(ici), ft(dcn))
                # one bincount for both leaf accumulators: trainer loads
                # in the first P*L bins, edge costs in the second
                two = bincount(
                    xp.concatenate([leaf_bins,
                                    leaf_bins + P * n_leaves], axis=0),
                    xp.concatenate([t_mds, t_mds * t_rate], axis=0),
                    2 * P * n_leaves)
                leaf_load = two[: P * n_leaves].reshape(P, n_leaves)
                edge_leaf = two[P * n_leaves:].reshape(P, n_leaves)
            elif not use_uniform:
                leaf_load = bincount(leaf_bins, t_mds,
                                     P * n_leaves).reshape(P, n_leaves)

            if use_uniform:
                # leaf slots: constant trainer load (+0 kid sum);
                # internal slots: +0 leaf part — both adds are exact
                lp = leaf_part[rows] if pooled else leaf_part
                child_load = lp + xp.sum(kid_mds, axis=2)
            else:
                child_load = xp.where(
                    is_leaf_slot[None],
                    leaf_load[:, slot_leaf_idx].astype(ft),
                    xp.sum(kid_mds, axis=2))
            load = host[0] + child_load
            if calibrated and cal_scale != 1.0:
                load = load * ft(cal_scale)
            delay = load / host[1]
            if penalty > 0:
                cap = host[2]
                over = xp.maximum(ft(0.0), load - cap)
                delay = delay * (1.0 + penalty * over /
                                 xp.maximum(cap, ft(1e-9)))
            if have_pods:
                delay = delay + xp.where(is_leaf_slot[None],
                                         edge_leaf[:, slot_leaf_idx
                                                   ].astype(ft),
                                         edge_int)
            if calibrated and any(cal_link):
                # per-part link charge: structural member counts for
                # non-duplicate rows; duplicate rows recount actual
                # trainers per leaf from the unplaced mask
                if use_uniform:
                    parts_f = static_parts[None]
                else:
                    leaf_cnt = bincount(
                        leaf_bins, xp.where(unplaced, ft(1.0), ft(0.0)),
                        P * n_leaves).reshape(P, n_leaves)
                    parts_f = xp.where(
                        is_leaf_slot[None],
                        leaf_cnt[:, slot_leaf_idx] + ft(1.0),
                        kid_parts[None])
                delay = delay + link_slot[None] * parts_f

            # per-level max, summed DEEPEST level first — the scalar
            # reference accumulates bottom-up, and float addition is not
            # associative, so the exact path must match its order
            if xp is np:
                level_max = np.maximum.reduceat(delay, level_starts_np,
                                                axis=1)
                out = level_max[:, ::-1].sum(axis=1)
            else:
                level_max = [xp.max(delay[:, a:b], axis=1)
                             for a, b in level_bounds]
                out = xp.sum(xp.stack(level_max[::-1], axis=1), axis=1)
            if train_add is not None:
                out = out + (train_add[rows] if pooled else train_add)
            return out

        return jax.jit(batch, static_argnames=()) if xp is jnp else batch

    @property
    def topology_version(self) -> int:
        """How many times :meth:`retarget` swapped the hierarchy (0 for
        a static run)."""
        return getattr(self, "_topology_version", 0)

    def retarget(self, hierarchy: Hierarchy) -> None:
        """Swap in a new hierarchy after an elastic resize.

        The elastic environments call this when the client population
        crosses the current tree's capacity: the SAME cost model object
        (strategies hold references to it) starts pricing rounds on the
        new topology, and the bumped ``topology_version`` joins the
        pool-mutation counter in :meth:`_client_token`, so every cached
        evaluator — per-slot leaf constants included — is rebuilt on the
        next call instead of serving stale-shape answers.
        """
        if hierarchy.total_clients != len(self.clients):
            raise ValueError(
                f"hierarchy expects {hierarchy.total_clients} clients, "
                f"pool has {len(self.clients)}")
        pod = getattr(self, "pod_of", None)
        if pod is not None and len(pod) != hierarchy.total_clients:
            raise ValueError(
                "cannot retarget a two-tier cost model across a pool "
                "resize: pod_of does not cover the new population")
        object.__setattr__(self, "hierarchy", hierarchy)
        object.__setattr__(self, "_topology_version",
                           self.topology_version + 1)

    def _calibration_terms(self) -> tuple:
        """(payload_scale, level_link, train_scale) — neutral
        ``(1.0, (), 0.0)`` on the base model; CalibratedCostModel
        overrides the fields. One tuple so every consumer (closure
        builder, pooled-evaluator compatibility check, Pallas gate)
        compares the same thing."""
        return (float(getattr(self, "payload_scale", 1.0)),
                tuple(float(b) for b in getattr(self, "level_link", ())
                      or ()),
                float(getattr(self, "train_scale", 0.0)))

    def _client_token(self) -> tuple:
        """O(1) fingerprint of the client attrs + topology baked into
        the cached evaluators — the pool's mutation version counter
        (bumped by attribute rebinds automatically; in-place editors
        call ``ClientPool.touch()``) plus the retarget counter, so
        neither in-place ClientPool edits nor elastic re-hierarchization
        can serve stale TPDs without hashing whole arrays per call."""
        return (id(self.clients), self.clients.version,
                self.topology_version)

    def _cached(self, attr: str, build):
        token = self._client_token()
        cached = getattr(self, attr, None)
        if cached is None or cached[0] != token:
            cached = (token, build())
            object.__setattr__(self, attr, cached)
        return cached[1]

    def _pallas_ok(self) -> bool:
        """The Pallas TPD kernel covers the base eq. 6/7 model (no pod
        edge costs, no trace-calibrated terms) and is compiled for the
        TPU only."""
        return getattr(self, "pod_of", None) is None and \
            self._calibration_terms() == (1.0, (), 0.0) and \
            jax.default_backend() == "tpu"

    def set_default_backend(self, backend: Optional[str]) -> None:
        """Pin what ``batch_tpd(backend=None)`` dispatches to — the
        ``EvalConfig.backend`` plumbing (``build_environment`` sets it
        on the models it constructs). ``None`` restores auto-selection.
        """
        if backend not in (None, "np", "jit", "pallas", "interpret"):
            raise ValueError(f"unknown batch_tpd backend {backend!r}; "
                             f"use None, 'np', 'jit', 'pallas' or "
                             f"'interpret'")
        object.__setattr__(self, "_default_backend", backend)

    def tpd_path(self, n_rows: int) -> str:
        """The path ``batch_tpd(backend=None)`` takes for ``n_rows``
        placements: the ``set_default_backend`` pin if any, else numpy
        up to ``_NP_FASTPATH_ELEMS`` placement-client elements, else the
        Pallas kernel where :meth:`_pallas_ok`, else jit'd XLA."""
        pinned = getattr(self, "_default_backend", None)
        if pinned is not None:
            return pinned
        if n_rows * self.hierarchy.total_clients <= self._NP_FASTPATH_ELEMS:
            return "np"
        return "pallas" if self._pallas_ok() else "jit"

    def batch_tpd(self, placements, backend: Optional[str] = None
                  ) -> np.ndarray:
        """(P, D) placements -> (P,) TPDs.

        ``backend``: ``None`` auto-selects (numpy below the fast-path
        threshold, the Pallas kernel on TPU for large batches, jit'd
        XLA otherwise); ``"np"`` / ``"jit"`` / ``"pallas"`` /
        ``"interpret"`` force a path. ``"pallas"`` compiles the kernel
        on TPU and interprets elsewhere; ``"interpret"`` forces the
        Pallas INTERPRETER even on the TPU — the CI escape hatch that
        exercises the kernel body on any host (pinned against
        ``kernels.ref.tpd_ref`` by the parity suite).
        A ``set_default_backend`` pin (EvalConfig plumbing) replaces
        the auto-selection, never an explicit ``backend=``.
        """
        placements = np.asarray(placements, np.int32)
        tracing.count("tpd.calls")
        if backend is None:
            backend = self.tpd_path(placements.shape[0])
        if backend == "np":
            fn = self._cached("_batch_tpd_np",
                              lambda: self._make_batch_tpd(np))
        elif backend == "jit":
            fn = self._cached("_batch_tpd_jax",
                              lambda: self._make_batch_tpd(jnp))
        elif backend in ("pallas", "interpret"):
            if getattr(self, "pod_of", None) is not None:
                raise ValueError("the Pallas TPD kernel does not cover "
                                 "two-tier pod edge costs; use "
                                 "backend='jit'")
            if self._calibration_terms() != (1.0, (), 0.0):
                raise ValueError("the Pallas TPD kernel does not cover "
                                 "trace-calibrated terms; use "
                                 "backend='jit'")
            if backend == "interpret":
                fn = self._cached(
                    "_batch_tpd_pl_int",
                    lambda: self._make_pallas_tpd(force_interpret=True))
            else:
                fn = self._cached("_batch_tpd_pl",
                                  lambda: self._make_pallas_tpd())
        else:
            raise ValueError(f"unknown batch_tpd backend {backend!r}; "
                             f"use None, 'np', 'jit', 'pallas' or "
                             f"'interpret'")
        return fn(placements)

    def _make_pallas_tpd(self, force_interpret: bool = False):
        """Closure running the TPD program (``kernels.tpd.tpd_scores``):
        the trainer-split leaf loads and the fused Pallas kernel in one
        jitted call. Per call the host uploads the (P, D) int32
        placements and reads back the (P,) TPDs; the attribute table is
        uploaded once, here, and stays on the device.

        The kernel is compiled on TPU and interpreted on every other
        backend; ``force_interpret`` interprets it on the TPU too (the
        ``backend="interpret"`` escape hatch).
        """
        from repro.kernels.tpd import tpd_scores
        h = self.hierarchy
        attrs = jnp.asarray(self._attr_stack(np.float32))   # (3, C)
        interpret = force_interpret or jax.default_backend() != "tpu"
        penalty = float(self.memory_penalty)

        def run(placements):
            with tracing.span("tpd.prologue"):
                placements = np.asarray(placements, np.int32)
            with tracing.span("tpd.transfer"):
                out = tpd_scores(placements, attrs, depth=h.depth,
                                 width=h.width, penalty=penalty,
                                 interpret=interpret)
            tracing.count("tpd.device_leaf_calls")
            with tracing.span("tpd.wait"):
                return np.asarray(out)

        return run

    def tpd_fast(self, placement) -> float:
        """Single-placement fast path: the cached EXACT (float64 numpy)
        vectorized evaluator on a batch of 1.

        Bit-identical to the scalar :meth:`tpd` for trees with width < 8
        (see ``_make_batch_tpd``), ~10-25x faster at 1k-10k clients —
        the Python trainer-assignment/cluster loops never run. This is
        what ``SimulatedEnvironment.step`` calls every round.
        """
        placements = np.asarray(placement, np.int32).reshape(1, -1)
        fn = self._cached(
            "_batch_tpd_exact",
            lambda: self._make_batch_tpd(np, dtype=np.float64))
        return float(fn(placements)[0])

    def batch_fitness(self, placements) -> np.ndarray:
        return -np.asarray(self.batch_tpd(placements))

    @classmethod
    def from_trace(cls, trace, *, hierarchy: Optional[Hierarchy] = None,
                   clients: Optional[ClientPool] = None,
                   holdout_rounds: int = 0) -> "CalibratedCostModel":
        """Fit a :class:`CalibratedCostModel` from a recorded
        :class:`repro.calibration.trace.TraceArtifact` (or a path to
        one). ``hierarchy``/``clients`` default to the shape and
        attribute snapshot stored in the trace; ``holdout_rounds``
        withholds the LAST k rounds from the fit (replay scores them as
        held-out). Delegates to ``repro.calibration.fit`` (imported
        lazily — calibration depends on this module, not vice versa)."""
        from repro.calibration.fit import cost_model_from_trace
        return cost_model_from_trace(trace, hierarchy=hierarchy,
                                     clients=clients,
                                     holdout_rounds=holdout_rounds)


class PooledTPDEvaluator:
    """ONE exact evaluation call for placements scored against DIFFERENT
    client pools — the batched sweep runner's engine.

    ``models`` are S cost models sharing hierarchy/penalty/pod topology
    but each wrapping its own (independently drifting) ClientPool — the
    per-seed environments of one sweep. ``tpds(placements, pool_idx)``
    scores placement row i against pool ``pool_idx[i]`` (default: row i
    vs pool i) in one float64 numpy call, bit-identical per row to
    ``models[s].tpd_fast(placements[i])`` — which is how the batched
    runner stays bit-identical to the sequential one.

    The stacked (A, S, C) attribute table is rebuilt lazily whenever any
    pool's mutation version changes (event schedules bump it), so
    mid-run churn/drift/straggler mutations are reflected in the very
    next call.

    ``shard`` controls device parallelism: ``"auto"`` (default) keeps
    the single-device float64 numpy path on 1 visible device — the
    bit-identity pin — and splits each call's placement rows across
    devices when more than one is visible (``shard_map`` row shards +
    segment-sum merge via ``fl.distributed.shard_rows``, float64 under
    ``jax.enable_x64``); ``"off"`` pins the numpy path
    unconditionally; ``"on"`` forces the sharded build even on 1
    device (tests). The sharded build re-jits whenever any pool's
    version moves (closure-baked attribute stack), so it pays off on
    static pools — drifting pools on 1 device stay on the numpy path
    anyway.
    """

    def __init__(self, models: Sequence[CostModel], shard: str = "auto"):
        if not models:
            raise ValueError("need at least one cost model")
        if shard not in ("auto", "on", "off"):
            raise ValueError(f"unknown shard mode {shard!r}; use "
                             f"'auto', 'on' or 'off'")
        m0 = models[0]
        for m in models[1:]:
            if m.hierarchy != m0.hierarchy:
                raise ValueError("pooled evaluation needs one shared "
                                 "hierarchy shape")
            if m.memory_penalty != m0.memory_penalty:
                raise ValueError("pooled evaluation needs one shared "
                                 "memory penalty")
            if type(m) is not type(m0):
                raise ValueError("pooled evaluation needs one cost-model "
                                 "type")
            pod, pod0 = getattr(m, "pod_of", None), \
                getattr(m0, "pod_of", None)
            if (pod is None) != (pod0 is None) or \
                    (pod is not None and not np.array_equal(pod, pod0)) or \
                    getattr(m, "ici_cost", 0.0) != \
                    getattr(m0, "ici_cost", 0.0) or \
                    getattr(m, "dcn_cost", 0.0) != \
                    getattr(m0, "dcn_cost", 0.0):
                raise ValueError("pooled evaluation needs one shared pod "
                                 "topology")
            if m._calibration_terms() != m0._calibration_terms():
                raise ValueError("pooled evaluation needs one shared "
                                 "calibration (payload_scale/level_link/"
                                 "train_scale)")
        self.models = list(models)
        self.shard = shard
        self._versions: Optional[tuple] = None
        self._fn = None
        self._shard_fn = None
        self._shard_sig: Optional[tuple] = None

    def _check_aligned(self) -> None:
        """Elastic runs retarget models in place; a rebuild must not mix
        topology epochs (the batched runner groups runs into
        same-hierarchy cohorts before pooling)."""
        for m in self.models[1:]:
            if m.hierarchy != self.models[0].hierarchy:
                raise ValueError("pooled evaluation needs one shared "
                                 "hierarchy shape")

    def tpds(self, placements, pool_idx=None) -> np.ndarray:
        placements = np.asarray(placements, np.int32)
        if self.shard != "off":
            ndev = jax.local_device_count()
            if self.shard == "on" or \
                    (ndev > 1 and placements.shape[0] >= ndev):
                return self._tpds_sharded(placements, pool_idx, ndev)
        versions = tuple(m._client_token() for m in self.models)
        if self._fn is None or versions != self._versions:
            self._check_aligned()
            attrs = np.stack(
                [m._attr_stack(np.float64) for m in self.models], axis=1)
            self._fn = self.models[0]._make_batch_tpd(
                np, dtype=np.float64, pool_attrs=attrs)
            self._versions = versions
        return self._fn(placements, pool_idx)

    def tpds_sharded(self, placements, pool_idx=None,
                     ndev: Optional[int] = None) -> np.ndarray:
        """The device-sharded pooled call, explicitly (what ``tpds``
        auto-dispatches to on multi-device hosts): placement rows split
        across a 1-D ``("rows",)`` mesh via ``fl.distributed.
        shard_rows`` — each device scores its shard through the same
        jit'd pooled closure and the full (P,) vector is reassembled by
        a segment-sum + psum merge. Runs in float64 under
        ``jax.enable_x64``; numerically it is the XLA
        build of the numpy exact path (same reduction ORDER per row —
        sliced per-level maxima summed deepest-first — so any deltas
        are non-associativity noise at f64, pinned ~1e-12 by the parity
        suite against the sequential ``tpds`` oracle)."""
        placements = np.asarray(placements, np.int32)
        return self._tpds_sharded(
            placements, pool_idx,
            jax.local_device_count() if ndev is None else int(ndev))

    def sharded_fn(self, mesh, n_rows: int):
        """The row-sharded float64 pooled evaluator over ``mesh``'s
        ``"rows"`` axis for ``n_rows`` placement rows: ``(placements,
        pool_idx) -> (n_rows,)``. Build and call it under
        ``jax.enable_x64(True)``."""
        from repro.fl.distributed import shard_rows
        self._check_aligned()
        attrs = np.stack([m._attr_stack(np.float64) for m in self.models],
                         axis=1)
        fn = self.models[0]._make_batch_tpd(jnp, dtype=np.float64,
                                            pool_attrs=attrs)
        return shard_rows(fn, mesh, n_rows)

    def _tpds_sharded(self, placements, pool_idx, ndev: int) -> np.ndarray:
        from repro.launch.mesh import make_mesh
        n_rows = placements.shape[0]
        rows = np.arange(n_rows) if pool_idx is None \
            else np.asarray(pool_idx)
        ndev = max(1, min(int(ndev), n_rows))
        versions = tuple(m._client_token() for m in self.models)
        sig = (versions, n_rows, placements.shape[1], ndev)
        with jax.enable_x64(True):
            if self._shard_fn is None or self._shard_sig != sig:
                self._shard_fn = self.sharded_fn(
                    make_mesh((ndev,), ("rows",),
                              devices=jax.local_devices()[:ndev]),
                    n_rows)
                self._shard_sig = sig
            out = self._shard_fn(jnp.asarray(placements),
                                 jnp.asarray(rows))
        return np.asarray(out, np.float64)


@dataclass(frozen=True)
class TwoTierCostModel(CostModel):
    """Eq. 6 extended with link-tier communication costs — the paper's
    cost model mapped onto the TPU pod topology (DESIGN.md §8).

    Every child->aggregator edge pays a per-payload transfer cost that
    depends on whether the two clients share a pod: intra-pod edges ride
    the ~50 GB/s ICI, cross-pod edges the ~10x slower DCN. A placement
    optimizer over this model learns *pod locality* with zero topology
    knowledge — the black-box TPD signal alone pushes aggregation
    subtrees inside pods (bench_two_tier.py measures exactly that).
    """
    pod_of: Optional[np.ndarray] = None   # (n_clients,) pod index
    ici_cost: float = 0.005               # delay per payload unit, same pod
    dcn_cost: float = 0.05                # delay per payload unit, cross-pod

    def _edge_cost(self, host: int, child: int) -> float:
        if self.pod_of is None:
            return 0.0
        same = self.pod_of[host] == self.pod_of[child]
        rate = self.ici_cost if same else self.dcn_cost
        return float(self.clients.mdatasize[child]) * rate

    def cluster_delay(self, host: int, children: Sequence[int]) -> float:
        base = super().cluster_delay(host, children)
        comm = sum(self._edge_cost(host, c) for c in children)
        return base + comm

    # batch_tpd/batch_fitness are inherited: the base vectorized path
    # reconstructs true child identities per particle, so the pod-aware
    # edge costs ride the same jit'd evaluator (no scalar fallback).

    def cross_pod_edges(self, placement) -> tuple:
        """(cross, total) aggregation edges — the locality metric.

        Vectorized (called per-round in the two-tier bench diagnostics):
        internal edges come straight from the placement's kid-slot
        gather; trainer edges from the canonical round-robin split
        (rank among unplaced ids, mod leaves) — no Python double loop.
        """
        h = self.hierarchy
        placement = np.asarray(placement, np.int64)
        C, D = h.total_clients, h.dimensions
        leaf_start = h.level_starts[h.depth - 1]
        # trainer -> leaf-aggregator edges (duplicate placement ids are
        # legal: they shrink the placed set, so count actual trainers)
        unplaced = np.ones(C, bool)
        unplaced[placement] = False
        trainers = np.nonzero(unplaced)[0]
        total = (D - 1) + len(trainers)  # every non-root member: 1 edge
        if self.pod_of is None:
            return 0, total
        pod = np.asarray(self.pod_of)
        # internal slot -> parent-slot edges
        kid_slots = np.arange(1, D)
        host_pod = pod[placement[(kid_slots - 1) // h.width]]
        cross = int(np.count_nonzero(host_pod != pod[placement[kid_slots]]))
        leaf_of = np.arange(len(trainers)) % h.n_leaves
        t_host_pod = pod[placement[leaf_start + leaf_of]]
        cross += int(np.count_nonzero(t_host_pod != pod[trainers]))
        return cross, total

    def _cross_pod_edges_ref(self, placement) -> tuple:
        """Scalar reference for :meth:`cross_pod_edges` (parity oracle)."""
        h = self.hierarchy
        placement = np.asarray(placement, np.int64)
        children = h.children_clients(placement)
        cross = total = 0
        for s in range(h.dimensions):
            host = int(placement[s])
            for c in children[s]:
                total += 1
                if self.pod_of is not None and \
                        self.pod_of[host] != self.pod_of[c]:
                    cross += 1
        return cross, total


@dataclass(frozen=True)
class CalibratedCostModel(CostModel):
    """Eq. 6/7 with trace-fitted parameters (``repro.calibration``).

    The emulated track's deterministic engine charges

        delay_cluster = (sum_members mdatasize / PAYLOAD_SCALE) / pspeed
                        + comm_latency * n_members
        train_c       = local_steps / pspeed_c

    none of which the analytic base model prices. The fitted twin adds
    exactly those degrees of freedom, all linear in trace features:

    * ``payload_scale`` — multiplies the eq. 6 payload (the emulated
      engine's ``1 / EQ6_PAYLOAD_SCALE``);
    * ``level_link`` — per-level delay per cluster member (the
      ``comm_latency`` hop term; one beta per tree level, the last
      entry covering any deeper level);
    * ``train_scale`` — work units per local-training pass; charged as
      ``train_scale * max_c(1 / pspeed_c)``, a placement-independent
      offset that makes predicted TPDs comparable to the emulated
      ``train + agg`` composition.

    Neutral values (1.0, (), 0.0) make every evaluator bit-identical to
    the base :class:`CostModel`. The vectorized path rides the SAME
    ``_make_batch_tpd`` closure (the calibrated branches switch on via
    ``_calibration_terms``), so ``batch_tpd``/``tpd_fast``/
    ``PooledTPDEvaluator`` — the PSO inner-loop surfaces — need no new
    plumbing. The Pallas kernel does not cover the calibrated terms;
    ``batch_tpd`` refuses ``backend='pallas'/'interpret'`` here.
    """
    payload_scale: float = 1.0
    level_link: Tuple[float, ...] = ()
    train_scale: float = 0.0

    def __post_init__(self):
        object.__setattr__(self, "level_link",
                           tuple(float(b) for b in self.level_link))

    def _link_cost(self, level: int, n_members: int) -> float:
        if not self.level_link:
            return 0.0
        beta = self.level_link[min(level, len(self.level_link) - 1)]
        return beta * n_members

    def calibrated_cluster_delay(self, host: int, children, level: int
                                 ) -> float:
        """Eq. 6 with the fitted payload scale, memcap penalty on the
        scaled payload, and the per-level per-member link charge."""
        mds = self.clients.mdatasize
        load = mds[host] + sum(mds[c] for c in children)
        load = load * self.payload_scale
        delay = load / self.clients.pspeed[host]
        if self.memory_penalty > 0:
            over = max(0.0, load - self.clients.memcap[host])
            delay *= 1.0 + self.memory_penalty * over / max(
                self.clients.memcap[host], 1e-9)
        return float(delay + self._link_cost(level, len(children) + 1))

    def train_time(self) -> float:
        """The fitted local-training bottleneck: placement-independent,
        so it never moves the argmin — it aligns predicted TPD with the
        emulated ``train + agg`` total."""
        if self.train_scale == 0.0:
            return 0.0
        return float(self.train_scale
                     * (1.0 / np.asarray(self.clients.pspeed)).max())

    def tpd(self, placement: Sequence[int]) -> float:
        """Scalar reference of the calibrated eq. 7 (the parity oracle
        the shared vectorized closure stays bit-identical to)."""
        h = self.hierarchy
        children = h.children_clients(placement)
        total = 0.0
        for level in range(h.depth - 1, -1, -1):
            worst = 0.0
            for s in range(h.level_starts[level],
                           h.level_starts[level + 1]):
                worst = max(worst, self.calibrated_cluster_delay(
                    int(placement[s]), children[s], level))
            total += worst
        return total + self.train_time()

    def cluster_delay(self, host: int, children: Sequence[int]) -> float:
        """Level-free callers get the scaled eq. 6 without the link
        charge (levels are a placement-walk property)."""
        mds = self.clients.mdatasize
        load = (mds[host] + sum(mds[c] for c in children)) \
            * self.payload_scale
        delay = load / self.clients.pspeed[host]
        if self.memory_penalty > 0:
            over = max(0.0, load - self.clients.memcap[host])
            delay *= 1.0 + self.memory_penalty * over / max(
                self.clients.memcap[host], 1e-9)
        return float(delay)
