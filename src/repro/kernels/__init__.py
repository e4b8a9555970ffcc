"""Pallas TPU kernels (+ jnp oracles) for the compute hot spots.

Each kernel ships three pieces: ``<name>.py`` (pl.pallas_call + explicit
BlockSpec VMEM tiling), an entry in ``ops.py`` (jit'd dispatch wrapper),
and an oracle in ``ref.py`` (pure jnp; the CPU/dry-run default path).
"""
from repro.kernels.ops import fedavg, fedavg_tree, flash_attention, fused_adamw, rglru_scan
from repro.kernels.tpd import batch_tpd_pallas

__all__ = ["fedavg", "fedavg_tree", "flash_attention", "fused_adamw",
           "rglru_scan", "batch_tpd_pallas"]
