"""Replication: device idle per engine step while the host stages
replication copies (``kf.repl.stage``) or ships them
(``kf.transport.flush``), from the program's spans, in ms."""
from pathlib import Path

from bench import program_spans as P

ROOT = Path(__file__).resolve().parents[2]


def read(run):
    prog = P.for_run(run, ROOT)
    if prog is None:
        return None
    steps = prog.named("engine.step")
    if not steps:
        return None
    spans = prog.named("repl.stage") + prog.named("transport.flush")
    return prog.idle_within(spans) / len(steps) * 1e3
