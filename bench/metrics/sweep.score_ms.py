"""Host ms per sweep round in the pooled scoring calls (the program's
``PooledTPDEvaluator.tpds``: each round's one call, the first of each
sweep with the evaluator's compile): the benchmark's ``score`` span."""


def read(run):
    s = run["spans"].get("score", 0.0)
    return s / run["stats"]["units"] * 1e3 if s > 0 else None
