"""Model step: the prompt tokens prefilled in the window per second times
2 x the weights one token multiplies, over the chip's bf16 peak. Counted
over real prompt tokens, not the padded buckets the program computes."""
from bench.roofline import model_flops


def read(run):
    t0, t1 = run.window
    tokens = sum(len(r["prompt"]) for r in run.measured
                 if t0 < r["first"] <= t1)
    if not tokens:
        return None
    return 100 * model_flops(run.conf, tokens) / run.seconds \
        / run.peaks["bf16_flops_per_s"]
