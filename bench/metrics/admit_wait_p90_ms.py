"""Data plane (admission): p90 over the requests admitted in the traced
window of their wait in the engine's admission queue, admit - arrival,
carried in microseconds on each request's admission span."""
from pathlib import Path

from bench import program_spans as P
from bench.harness import percentile

ROOT = Path(__file__).resolve().parents[2]


def read(run):
    prog = P.for_run(run, ROOT)
    if prog is None:
        return None
    waits = P.admission_waits(prog, "queue_us")
    return percentile(waits, 90) / 1e3 if waits else None
