"""Data plane: device idle per decode step that the host's own work
leaves, from the program's spans: the mean over ``kf.decode`` spans with
live slots of the first device's idle inside their ``prepare``, ``launch``
and ``finish`` children, in ms. ``decode_host_idle_ms.<cell kind>``
variants read the same."""
from pathlib import Path

from bench import program_spans as P

ROOT = Path(__file__).resolve().parents[2]


def read(run):
    prog = P.for_run(run, ROOT)
    if prog is None:
        return None
    steps = [s for s in prog.named("decode") if s.stats.get("slots", 0) > 0]
    if not steps:
        return None
    parts = [c for s in steps for c in prog.children(s, P.DECODE_PARTS)]
    return prog.idle_within(parts) / len(steps) * 1e3
