"""Check the trace reduction on a trace recorded on the chip (CPU only).

    python3 bench/check_trace.py [bench/testdata/<name>.xplane.pb.gz ...]

For each recorded trace, ``harness.trace`` condenses and reduces it, and
this script recomputes the same numbers from the condensed events by
brute force — the idle share from a per-nanosecond-interval sweep of
every op edge, device time per named program by a plain sum, and each
idle gap's span by a scan of every span — and compares them with the
reduction and with the values recorded beside the trace
(``<name>.expect.json``, which also names the host spans that the
run opened). Exit 0 when all agree.
"""
from __future__ import annotations

import gzip
import json
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from harness import trace  # noqa: E402


def brute(ev: dict) -> dict:
    (lo, hi), = [(s, s + d) for n, s, d in ev["host"] if n == "window"]
    out = {"window_s": (hi - lo) / 1e9, "chips": {}}
    for name, dev in ev["devices"].items():
        edges = sorted({lo, hi} | {x for _, s, d in dev["ops"]
                                   for x in (s, s + d) if lo < x < hi})
        busy = 0
        idle = []
        for a, b in zip(edges[:-1], edges[1:]):
            covered = any(s <= a and s + d >= b for _, s, d in dev["ops"])
            if covered:
                busy += b - a
            elif idle and idle[-1][1] == a:
                idle[-1][1] = b
            else:
                idle.append([a, b])
        progs: dict = {}
        for n, s, d in dev["modules"]:
            if s + d > lo and s < hi:
                k = trace.program_name(n)
                progs[k] = progs.get(k, 0.0) + d / 1e9
        out["chips"][name] = {"busy_s": busy / 1e9, "programs": progs,
                              "idle": idle}
    return out


def span_at(ev: dict, t: float) -> str:
    cover = [(d, n) for n, s, d in ev["host"]
             if n != "window" and s <= t <= s + d]
    return min(cover)[1] if cover else "outside spans"


def check(path: Path) -> list:
    expect = json.loads(Path(str(path).replace(".xplane.pb.gz",
                                               ".expect.json")).read_text())
    ev = trace.condense(gzip.decompress(path.read_bytes()), expect["spans"])
    red = trace.reduce(ev)
    ref = brute(ev)
    bad = []
    if abs(red["window_s"] - ref["window_s"]) > 1e-12:
        bad.append("window")
    for name, c in ref["chips"].items():
        r = red["chips"][name]
        if abs(r["busy_s"] - c["busy_s"]) > 1e-9:
            bad.append(f"{name} busy {r['busy_s']} vs {c['busy_s']}")
        for k, v in c["programs"].items():
            if abs(r["programs"].get(k, 0.0) - v) > 1e-9:
                bad.append(f"{name} program {k}")
    gaps = sorted(((b - a) / 1e9, span_at(ev, (a + b) / 2))
                  for a, b in ref["chips"][red["busiest"]]["idle"])[::-1]
    want = [[n, t] for t, n in gaps[:10]]
    got = red["breakdown"]["idle_gaps"]
    if [n for n, _ in got] != [n for n, _ in want] or any(
            abs(a[1] - b[1]) > 1e-9 for a, b in zip(got, want)):
        bad.append(f"idle gaps {got} vs {want}")
    for k in ("window_s", "busy_s"):
        if abs(red[k] - expect[k]) > 1e-9:
            bad.append(f"{k} {red[k]} vs recorded {expect[k]}")
    if red["breakdown"] != expect["breakdown"]:
        bad.append("breakdown differs from the recorded one")
    return bad


def main(argv=None) -> int:
    paths = [Path(p) for p in (argv if argv is not None else sys.argv[1:])] \
        or sorted((BENCH / "testdata").glob("*.xplane.pb.gz"))
    if not paths:
        print("check_trace: no recorded trace", file=sys.stderr)
        return 1
    rc = 0
    for p in paths:
        bad = check(p)
        print(f"{p.name}: {'ok' if not bad else 'FAILED: ' + '; '.join(bad)}")
        rc = rc or bool(bad)
    return rc


if __name__ == "__main__":
    sys.exit(main())
