"""§Roofline: per (arch x shape x mesh) three-term roofline from the
dry-run artifacts.

    compute term    = HLO_FLOPs / (chips x peak bf16 FLOP/s)
    memory term     = HLO_bytes / (chips x peak HBM bytes/s)
    collective term = collective_bytes / (chips x peak ICI bytes/s)

The peaks are those of the record's ``device_kind`` (the chip the
dry-run meshes model), from ``repro.launch.mesh.CHIP_PEAKS``; a kind
without published peaks is an error.

HLO_FLOPs / HLO_bytes / collective_bytes come from the trip-count-aware
HLO walker (utils/hlo.py) over the compiled module — per-device numbers,
so the "chips" division is already folded in (the artifact stores
per-partition HLO costs).

Also reported per row:
  * MODEL_FLOPS = 6·N·D (train) or 2·N·D (inference fwd), with N_active
    for MoE — the "useful" FLOPs;
  * MODEL_FLOPS / HLO_FLOPs (how much of compiled compute is useful —
    catches remat/attention/dispatch overhead; remat alone gives ~0.75);
  * the dominant term and a one-line lever on it.

CPU-HLO caveat (documented in EXPERIMENTS.md): XLA's CPU pipeline
normalizes bf16 to f32, so byte/collective terms are ~2x upper bounds
wherever the TPU build would keep bf16.
"""
from __future__ import annotations

import json
from pathlib import Path

import jax

from repro.configs import ASSIGNED, SHAPES, get_config
from repro.launch.mesh import chip_peaks

ART = Path(__file__).resolve().parent.parent / "artifacts" / "dryrun"
OUT = Path(__file__).resolve().parent.parent / "artifacts" / "benchmarks"


def model_params(arch: str) -> tuple:
    """(N_total, N_active) parameter counts from eval_shape."""
    from repro.models import get_model
    cfg = get_config(arch)
    model = get_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    n_total = sum(x.size for x in jax.tree.leaves(shapes))
    n_active = n_total
    if cfg.moe is not None:
        e, k, f, d = (cfg.moe.n_experts, cfg.moe.top_k,
                      cfg.moe.d_ff_expert, cfg.d_model)
        layers = cfg.n_layers
        n_active = n_total - layers * (e - k) * 3 * d * f
    return n_total, n_active


def model_flops(arch: str, shape_name: str, chips: int) -> float:
    """Useful FLOPs per step per chip: 6ND train / 2ND serve-fwd."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    _, n_active = model_params(arch)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens / chips
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens / chips
    # decode: ONE token per sequence
    return 2.0 * n_active * shape.global_batch / chips


LEVERS = {
    "compute": "raise MXU utilization: bigger per-chip tiles, fewer "
               "remat recomputes, fuse attention (Pallas kernel on TPU)",
    "memory": "cut HBM traffic: bf16 residual/cache, fewer elementwise "
              "round-trips (fusion), sequence-sharded activations",
    "collective": "reshard: sequence-parallel activations "
                  "(reduce-scatter instead of all-reduce), EP dispatch "
                  "instead of dense fallback, overlap collectives",
}


def analyze(record: dict) -> dict:
    prof = record["profile"]
    arch, shape, mesh = record["arch"], record["shape"], record["mesh"]
    chips = record["chips"]
    peaks = chip_peaks(record.get("device_kind"))
    t_compute = prof["flops"] / peaks["flops_bf16"]
    t_memory = prof["bytes_accessed"] / peaks["hbm_bw"]
    t_coll = prof["collective_bytes"] / peaks["ici_bw"]
    terms = {"compute": t_compute, "memory": t_memory,
             "collective": t_coll}
    dominant = max(terms, key=terms.get)
    mf = model_flops(arch, shape, chips)
    bound = max(terms.values())
    return {
        "arch": arch, "shape": shape, "mesh": mesh, "mode": record["mode"],
        "compute_s": t_compute, "memory_s": t_memory,
        "collective_s": t_coll,
        "dominant": dominant,
        "model_flops_per_chip": mf,
        "useful_flops_ratio": mf / max(prof["flops"], 1.0),
        "roofline_fraction": mf / peaks["flops_bf16"] / max(bound, 1e-12),
        "lever": LEVERS[dominant],
    }


def load_records(mesh: str = "16x16") -> list:
    recs = []
    for arch in ASSIGNED:
        for shape in SHAPES:
            p = ART / f"{arch}__{shape}__{mesh}.json"
            if p.exists():
                recs.append(json.loads(p.read_text()))
    return recs


def render_table(rows: list) -> str:
    hdr = (f"| {'arch':24s} | {'shape':11s} | {'mode':10s} | "
           f"{'compute_s':>9s} | {'memory_s':>9s} | {'coll_s':>9s} | "
           f"{'dominant':10s} | {'useful':>6s} | {'roofl%':>6s} |")
    sep = "|" + "-" * (len(hdr) - 2) + "|"
    lines = [hdr, sep]
    for r in rows:
        lines.append(
            f"| {r['arch']:24s} | {r['shape']:11s} | {r['mode']:10s} | "
            f"{r['compute_s']:9.4f} | {r['memory_s']:9.4f} | "
            f"{r['collective_s']:9.4f} | {r['dominant']:10s} | "
            f"{r['useful_flops_ratio']:6.2f} | "
            f"{100 * r['roofline_fraction']:6.1f} |")
    return "\n".join(lines)


def main(mesh: str = "16x16") -> dict:
    recs = load_records(mesh)
    if not recs:
        print(f"[roofline] no dry-run artifacts for mesh {mesh} under {ART}; "
              f"run `python -m repro.launch.dryrun --all` first")
        return {"rows": []}
    rows = [analyze(r) for r in recs]
    print(f"== Roofline ({mesh}, {len(rows)} combos) — "
          f"seconds per step per chip ==")
    print(render_table(rows))
    worst = sorted(rows, key=lambda r: r["roofline_fraction"])[:3]
    coll_bound = sorted(rows, key=lambda r: -r["collective_s"])[:3]
    print("\nworst roofline fraction:",
          [(r["arch"], r["shape"]) for r in worst])
    print("most collective-bound:",
          [(r["arch"], r["shape"]) for r in coll_bound])
    OUT.mkdir(parents=True, exist_ok=True)
    out = {"mesh": mesh, "rows": rows,
           "worst_roofline": [(r["arch"], r["shape"]) for r in worst],
           "most_collective_bound": [(r["arch"], r["shape"])
                                     for r in coll_bound]}
    (OUT / f"roofline_{mesh}.json").write_text(json.dumps(out, indent=1))
    return out


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="16x16")
    args = ap.parse_args()
    main(mesh=args.mesh)
