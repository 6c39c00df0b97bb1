"""Front end (EngineService): p90 over measured requests of the wait from
when a request was due until the engine admitted it."""
from bench.harness import percentile


def read(run):
    waits = [r["admit"] - r["due"] for r in run.measured if r["admit"] > 0]
    return percentile(waits, 90) if waits else None
