"""RPL001: the machine-readable parity-oracle registry.

Every vectorized/batched public entry point and every Pallas kernel in
the hot packages must appear here as the ``fast`` half of an
:class:`OraclePair`, pointing at its scalar/sequential oracle and at
least one test file exercising both. The check fails when

* a ``batch_*`` / ``batched_*`` / ``*_batched`` public def or a
  function calling ``pl.pallas_call`` lands unregistered (suppressible
  at the def site with a reasoned ``RPL001`` pragma — e.g. a shape
  helper that merely matches the name pattern);
* a registry entry's ``fast`` or ``oracle`` symbol no longer resolves
  (registry rot — deleting ``tpd_ref`` fails the pass);
* a listed test file is missing, or none of them textually mention both
  the fast and oracle base names.

Symbols are AST-resolved from source, never imported — the pass runs in
the lint tier before jax is available.
"""
from __future__ import annotations

import ast
import re
from dataclasses import dataclass
from pathlib import Path
from typing import Iterator, List, Optional, Sequence, Tuple

from repro.analysis.engine import FileContext, Violation

REGISTRY_PATH = "src/repro/analysis/parity.py"

# packages whose batch-pattern defs and Pallas kernels must be paired
SCAN_PREFIXES = (
    "src/repro/core/",
    "src/repro/fl/",
    "src/repro/kernels/",
    "src/repro/experiments/",
    "src/repro/online/",
    "src/repro/faults/",
    "src/repro/calibration/",
)
_BATCH_NAME = re.compile(r"^batch(ed)?_|_batched$")


@dataclass(frozen=True)
class OraclePair:
    """``fast`` and ``oracle`` are ``module:qualname`` strings."""

    fast: str
    oracle: str
    tests: Tuple[str, ...]


REGISTRY: Tuple[OraclePair, ...] = (
    # --- swarm optimizer: vectorized run vs. the sequential reference ---
    OraclePair(
        fast="repro.core.pso:FlagSwapPSO.run",
        oracle="repro.core.pso:FlagSwapPSO._run_reference",
        tests=("tests/test_scale_parity.py",),
    ),
    OraclePair(
        fast="repro.core.pso:FlagSwapPSO._dedup_fix",
        oracle="repro.core.pso:FlagSwapPSO._dedup_ints",
        tests=("tests/test_scale_parity.py",),
    ),
    # --- cost model: batched/pooled evaluators vs. the scalar eq. 6-7 ---
    OraclePair(
        fast="repro.core.cost_model:CostModel.batch_tpd",
        oracle="repro.core.cost_model:CostModel.tpd",
        tests=("tests/test_cost_model.py", "tests/test_scale_parity.py"),
    ),
    OraclePair(
        fast="repro.core.cost_model:CostModel.tpd_fast",
        oracle="repro.core.cost_model:CostModel.tpd",
        tests=("tests/test_scale_parity.py",),
    ),
    OraclePair(
        fast="repro.core.cost_model:CostModel.batch_fitness",
        oracle="repro.core.cost_model:CostModel.fitness",
        tests=("tests/test_scale_parity.py",),
    ),
    OraclePair(
        fast="repro.core.cost_model:PooledTPDEvaluator.tpds",
        oracle="repro.core.cost_model:CostModel.tpd_fast",
        tests=("tests/test_scale_parity.py",),
    ),
    OraclePair(
        fast="repro.core.cost_model:PooledTPDEvaluator.tpds_sharded",
        oracle="repro.core.cost_model:CostModel.tpd_fast",
        tests=("tests/test_scale_parity.py",),
    ),
    OraclePair(
        fast="repro.core.cost_model:TwoTierCostModel.cross_pod_edges",
        oracle="repro.core.cost_model:TwoTierCostModel._cross_pod_edges_ref",
        tests=("tests/test_scale_parity.py",),
    ),
    # --- aggregation: segment-summed tree fedavg vs. sequential walk ---
    OraclePair(
        fast="repro.fl.aggregation:batched_hierarchical_fedavg",
        oracle="repro.fl.aggregation:hierarchical_fedavg",
        tests=("tests/test_round_engine.py",),
    ),
    # --- experiment runner: lockstep batched sweep vs. one-run loop ---
    OraclePair(
        fast="repro.experiments.runner:run_batched",
        oracle="repro.experiments.runner:run_single",
        tests=("tests/test_analysis_sanitize.py",),
    ),
    # --- online track: staleness-weighted async merge vs. scalar loop ---
    OraclePair(
        fast="repro.online.async_fedavg:staleness_weights",
        oracle="repro.online.async_fedavg:_staleness_weights_ref",
        tests=("tests/test_online.py",),
    ),
    OraclePair(
        fast="repro.online.async_fedavg:async_merge_batched",
        oracle="repro.online.async_fedavg:_async_merge_ref",
        tests=("tests/test_online.py",),
    ),
    # --- calibration: vectorized cluster-delay surrogate vs. scalar ---
    OraclePair(
        fast="repro.calibration.fit:batch_predict_cluster_delay",
        oracle="repro.calibration.fit:_predict_cluster_delay_ref",
        tests=("tests/test_calibration.py",),
    ),
    # --- fault track: quorum-gated participation-damped merge ---
    OraclePair(
        fast="repro.faults.tolerance:quorum_merge_batched",
        oracle="repro.faults.tolerance:_quorum_merge_ref",
        tests=("tests/test_faults.py",),
    ),
    # --- Pallas kernels: each entry point vs. its jnp oracle ---
    OraclePair(
        fast="repro.kernels.tpd:batch_tpd_pallas",
        oracle="repro.kernels.ref:tpd_ref",
        tests=("tests/test_scale_parity.py",),
    ),
    OraclePair(
        fast="repro.kernels.tpd:leaf_loads",
        oracle="repro.kernels.ref:leaf_loads_ref",
        tests=("tests/test_scale_parity.py",),
    ),
    OraclePair(
        fast="repro.kernels.fedavg:fedavg_batched_pallas",
        oracle="repro.kernels.ref:fedavg_ref",
        tests=("tests/test_kernels.py",),
    ),
    OraclePair(
        fast="repro.kernels.fedavg:fedavg_pallas",
        oracle="repro.kernels.ref:fedavg_ref",
        tests=("tests/test_kernels.py",),
    ),
    OraclePair(
        fast="repro.kernels.flash_attention:flash_attention_pallas",
        oracle="repro.kernels.ref:flash_attention_ref",
        tests=("tests/test_kernels.py",),
    ),
    OraclePair(
        fast="repro.kernels.rglru:rglru_scan_pallas",
        oracle="repro.kernels.ref:rglru_scan_ref",
        tests=("tests/test_kernels.py",),
    ),
    OraclePair(
        fast="repro.kernels.fused_adamw:fused_adamw_pallas",
        oracle="repro.kernels.ref:fused_adamw_ref",
        tests=("tests/test_kernels.py",),
    ),
)


def module_rel_path(module: str) -> str:
    return "src/" + module.replace(".", "/") + ".py"


def resolve_symbol(
    contexts_by_rel: dict, symbol: str
) -> Optional[ast.FunctionDef]:
    """AST-resolve ``module:Qual.name`` against the scanned tree."""
    module, _, qualname = symbol.partition(":")
    ctx = contexts_by_rel.get(module_rel_path(module))
    if ctx is None:
        return None
    node: ast.AST = ctx.tree
    for part in qualname.split("."):
        for child in ast.iter_child_nodes(node):
            if (
                isinstance(
                    child, (ast.ClassDef, ast.FunctionDef, ast.AsyncFunctionDef)
                )
                and child.name == part
            ):
                node = child
                break
        else:
            return None
    if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
        return node
    return None


def _calls_pallas(fn: ast.AST, ctx: FileContext) -> bool:
    for sub in ast.walk(fn):
        if (
            isinstance(sub, ast.Call)
            and isinstance(sub.func, ast.Attribute)
            and sub.func.attr == "pallas_call"
            and ctx.enclosing_function(sub) is fn
        ):
            return True
    return False


def _iter_defs(
    ctx: FileContext,
) -> Iterator[Tuple[str, ast.FunctionDef]]:
    """(qualname, node) for module-level defs and class methods."""
    for child in ast.iter_child_nodes(ctx.tree):
        if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield child.name, child
        elif isinstance(child, ast.ClassDef):
            for sub in ast.iter_child_nodes(child):
                if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    yield f"{child.name}.{sub.name}", sub


def check(
    contexts: Sequence[FileContext],
    registry: Optional[Sequence[OraclePair]] = None,
    root: Optional[Path] = None,
) -> List[Violation]:
    by_rel = {ctx.rel: ctx for ctx in contexts}
    if registry is None:
        # default registry only binds when the scan covers this repo's
        # tree (partial scans / foreign roots can't resolve its symbols)
        registry = REGISTRY if REGISTRY_PATH in by_rel else ()
    out: List[Violation] = []

    registered = set()
    for pair in registry:
        module, _, qualname = pair.fast.partition(":")
        registered.add((module_rel_path(module), qualname))
        for role, symbol in (("fast", pair.fast), ("oracle", pair.oracle)):
            if resolve_symbol(by_rel, symbol) is None:
                mod_rel = module_rel_path(symbol.partition(":")[0])
                out.append(
                    Violation(
                        REGISTRY_PATH,
                        1,
                        "RPL001",
                        f"registry {role} symbol {symbol!r} does not resolve "
                        f"in {mod_rel} — stale entry or deleted oracle",
                    )
                )
        fast_base = pair.fast.partition(":")[2].rpartition(".")[2]
        oracle_base = pair.oracle.partition(":")[2].rpartition(".")[2]
        mentioned = False
        for test in pair.tests:
            tctx = by_rel.get(test)
            if tctx is not None:
                text: Optional[str] = tctx.source
            elif root is not None and (root / test).is_file():
                text = (root / test).read_text()
            else:
                text = None
            if text is None:
                out.append(
                    Violation(
                        REGISTRY_PATH,
                        1,
                        "RPL001",
                        f"registry entry {pair.fast!r} lists missing test "
                        f"file {test!r}",
                    )
                )
                continue
            if fast_base in text and oracle_base in text:
                mentioned = True
        if not mentioned:
            out.append(
                Violation(
                    REGISTRY_PATH,
                    1,
                    "RPL001",
                    f"no listed test exercises both {fast_base!r} and its "
                    f"oracle {oracle_base!r} for entry {pair.fast!r}",
                )
            )

    for ctx in contexts:
        if not ctx.rel.startswith(SCAN_PREFIXES):
            continue
        for qualname, fn in _iter_defs(ctx):
            base = qualname.rpartition(".")[2]
            is_batch = not base.startswith("_") and _BATCH_NAME.search(base)
            if not is_batch and not _calls_pallas(fn, ctx):
                continue
            if (ctx.rel, qualname) in registered:
                continue
            out.append(
                Violation(
                    ctx.rel,
                    fn.lineno,
                    "RPL001",
                    f"{qualname} looks like a vectorized/Pallas entry point "
                    "but has no parity-oracle registry entry "
                    "(analysis/parity.py) — register it with its scalar "
                    "oracle and a test covering both",
                )
            )
    return out
