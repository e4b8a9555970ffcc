"""Share of its roofline that the held experts' grouped FFN reaches on
the chip: the least time of one round's expert work (the larger of its
operations over the bf16 peak and its bytes over the HBM bandwidth,
``harness.moe_work`` from the held token-expert pairs the program
counted) over the device time of the operations named ``gmm`` or
``tgmm`` (the grouped matmul kernel, forward and backward) per round."""


def read(run):
    t, f = run["trace"], run["facts"]
    if not t or not t["chips"] or "expert_ops_per_unit" not in f:
        return None
    ops = t["chips"][t["busiest"]]["ops"]
    dev = sum(v for k, v in ops.items() if "gmm" in k.rsplit("/", 1)[-1])
    if dev <= 0:
        return None
    pk = run["peaks"]
    least = max(f["expert_ops_per_unit"] / pk["flops_bf16"],
                f["expert_bytes_per_unit"] / pk["hbm_bytes_s"])
    return 100.0 * least * run["stats"]["units"] / dev
