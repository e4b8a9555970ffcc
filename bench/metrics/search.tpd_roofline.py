"""Share of its roofline that TPD scoring reaches on the chip.

The least time of one iteration's scoring is the larger of the eq. 6/7
operations over the bf16 peak and the required bytes over the HBM
bandwidth (``harness.work.tpd_work``; at ``large-10k`` sizes the bytes
bound it). It is divided by the device time of the scoring programs in
the trace (names holding ``tpd`` or ``jit_batch``: the Pallas kernel's
jit, or the XLA build of the evaluator) per iteration."""
PROGRAMS = ("tpd", "jit_batch")


def read(run):
    t, f = run["trace"], run["facts"]
    if not t or not t["chips"] or "tpd_ops_per_unit" not in f:
        return None
    progs = t["chips"][t["busiest"]]["programs"]
    dev = sum(v for k, v in progs.items() if any(p in k for p in PROGRAMS))
    if dev <= 0:
        return None
    pk = run["peaks"]
    least = max(f["tpd_ops_per_unit"] / pk["flops_bf16"],
                f["tpd_bytes_per_unit"] / pk["hbm_bytes_s"])
    return 100.0 * least * run["stats"]["units"] / dev
