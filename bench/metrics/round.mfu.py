"""The whole round's share of the chip's peak: model FLOPs per round (6N
per trained sample, 2N per evaluated sample) times rounds per second of
the window, over the bf16 peak of the device kind."""


def read(run):
    f = run["facts"].get("flops_per_unit")
    if not f:
        return None
    rate = run["stats"]["units"] / run["stats"]["elapsed"]
    return 100.0 * f * rate / (run["peaks"]["flops_bf16"] * run["chips"])
