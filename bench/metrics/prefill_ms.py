"""Data plane: median time from admission to the first token (the
monolithic prefill, its host sync included)."""
from bench.harness import percentile


def read(run):
    t = [(r["first"] - r["admit"]) * 1e3 for r in run.measured
         if r["first"] > 0 and r["admit"] > 0]
    return percentile(t, 50) if t else None
