"""Multi-pod dry-run: prove every (architecture x input shape x mesh)
combination lowers, compiles, and fits — without any TPU.

For each combination this driver:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod)
     out of 512 placeholder host devices (XLA_FLAGS below — set before
     ANY jax import);
  2. builds the step bundle (the FL round / prefill / decode step with
     its ShapeDtypeStruct inputs and shardings — launch/specs.py);
  3. ``jax.jit(fn, in_shardings, out_shardings).lower(*args).compile()``;
  4. records ``memory_analysis()``, ``cost_analysis()`` and the summed
     collective bytes from the optimized HLO into a JSON artifact that
     the roofline benchmark (§Roofline) consumes.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch granite-8b \
        --shape train_4k [--multi-pod]
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""
import os

# 512 placeholder host devices — MUST be set before ANY jax import,
# which is why every import below carries a noqa: E402
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

import jax  # noqa: E402

from repro.configs import ASSIGNED, SHAPES  # noqa: E402
from repro.launch.mesh import (  # noqa: E402
    TARGET_DEVICE_KIND,
    make_mesh,
    make_production_mesh,
    mesh_chip_count,
)
from repro.launch.specs import build_bundle  # noqa: E402
from repro.utils.hlo import count_hlo_ops, profile_hlo  # noqa: E402

DEFAULT_OUT = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"


def _memory_dict(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception as e:  # some backends do not implement it
        return {"error": repr(e)}
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes",
              "alias_size_in_bytes", "host_argument_size_in_bytes",
              "host_output_size_in_bytes", "host_temp_size_in_bytes"):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    if not out:
        out["repr"] = repr(ma)
    return out


def _cost_dict(compiled) -> dict:
    try:
        ca = compiled.cost_analysis()
    except Exception as e:
        return {"error": repr(e)}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return {k: float(v) for k, v in ca.items()
            if isinstance(v, (int, float))}


def dryrun_one(arch: str, shape: str, multi_pod: bool = False,
               out_dir: Path = DEFAULT_OUT, verbose: bool = True,
               placement=None, force_mode=None,
               seq_shard: bool = True, mesh_shape=None) -> dict:
    if mesh_shape is not None:
        d, m = mesh_shape
        mesh = make_mesh((d, m), ("data", "model"))
        mesh_name = f"{d}x{m}"
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
        mesh_name = "2x16x16" if multi_pod else "16x16"
    t0 = time.perf_counter()
    bundle = build_bundle(arch, shape, mesh, placement=placement,
                          force_mode=force_mode, seq_shard=seq_shard)
    t_build = time.perf_counter() - t0

    jitted = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                     out_shardings=bundle.out_shardings)
    t0 = time.perf_counter()
    lowered = jitted.lower(*bundle.args)
    t_lower = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0

    hlo = compiled.as_text()
    t0 = time.perf_counter()
    prof = profile_hlo(hlo)
    t_profile = time.perf_counter() - t0
    record = {
        "arch": arch, "shape": shape, "mesh": mesh_name,
        "chips": mesh_chip_count(mesh),
        "device_kind": TARGET_DEVICE_KIND,
        "kind": bundle.kind, "mode": bundle.mode, "meta": bundle.meta,
        "memory": _memory_dict(compiled),
        "cost": _cost_dict(compiled),          # XLA (loop-bodies-once)
        "profile": prof.as_dict(),             # trip-count-aware walker
        "hlo_ops": count_hlo_ops(hlo),
        "timings": {"build_s": t_build, "lower_s": t_lower,
                    "compile_s": t_compile, "profile_s": t_profile},
    }
    out_dir.mkdir(parents=True, exist_ok=True)
    path = out_dir / f"{arch}__{shape}__{mesh_name}.json"
    path.write_text(json.dumps(record, indent=1))
    if verbose:
        mem = record["memory"]
        print(f"[dryrun] {arch} x {shape} x {mesh_name} ({bundle.mode}): "
              f"OK in {t_lower + t_compile:.1f}s | "
              f"args={mem.get('argument_size_in_bytes', 0) / 2**30:.2f}GiB "
              f"temp={mem.get('temp_size_in_bytes', 0) / 2**30:.2f}GiB | "
              f"flops={prof.flops:.3g} bytes={prof.bytes_accessed:.3g} "
              f"coll={prof.collective_bytes / 2**20:.1f}MiB")
    return record


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, help="architecture id")
    ap.add_argument("--shape", default=None, help="input shape name")
    ap.add_argument("--all", action="store_true",
                    help="run every assigned (arch x shape)")
    ap.add_argument("--multi-pod", action="store_true",
                    help="use the 2x16x16 (512-chip) mesh")
    ap.add_argument("--out", default=str(DEFAULT_OUT))
    ap.add_argument("--force-mode", default=None,
                    choices=(None, "fl_replica", "standard"))
    ap.add_argument("--no-seq-par", action="store_true",
                    help="disable sequence-parallel activations (the "
                         "pre-optimization baseline, for A/B)")
    ap.add_argument("--mesh-shape", default=None,
                    help="override single-pod mesh as 'DATA,MODEL' "
                         "(256 chips total), e.g. 32,8 — §Perf layouts")
    args = ap.parse_args()

    out_dir = Path(args.out)
    combos = []
    if args.all:
        combos = [(a, s) for a in ASSIGNED for s in SHAPES]
    else:
        if not args.arch or not args.shape:
            ap.error("need --arch and --shape (or --all)")
        combos = [(args.arch, args.shape)]

    failures = []
    for arch, shape in combos:
        try:
            ms = None
            if args.mesh_shape:
                ms = tuple(int(x) for x in args.mesh_shape.split(","))
            dryrun_one(arch, shape, multi_pod=args.multi_pod,
                       out_dir=out_dir, force_mode=args.force_mode,
                       seq_shard=not args.no_seq_par, mesh_shape=ms)
        except Exception:
            failures.append((arch, shape))
            print(f"[dryrun] {arch} x {shape} FAILED:")
            traceback.print_exc()
    if failures:
        print(f"[dryrun] {len(failures)} FAILURES: {failures}")
        return 1
    print(f"[dryrun] all {len(combos)} combination(s) passed")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
