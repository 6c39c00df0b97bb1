"""Front end (EngineService): p90 over the requests admitted in the traced
window of the wait for the service's lock, arrival - submit
(``Request.submit_time`` is stamped before the lock, ``arrival_time``
under it), carried in microseconds on each request's admission span."""
from pathlib import Path

from bench import program_spans as P
from bench.harness import percentile

ROOT = Path(__file__).resolve().parents[2]


def read(run):
    prog = P.for_run(run, ROOT)
    if prog is None:
        return None
    waits = P.admission_waits(prog, "lock_wait_us")
    return percentile(waits, 90) / 1e3 if waits else None
