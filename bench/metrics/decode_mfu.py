"""Model step: the window's decode tokens per second times 2 x the weights
one token multiplies (a mixture's routed experts only), over the chip's
bf16 peak. Decode tokens are the window's output tokens less the first
tokens, which prefill makes."""
from bench.roofline import model_flops


def read(run):
    t0, t1 = run.window
    firsts = sum(1 for r in run.measured if t0 < r["first"] <= t1)
    decoded = run.tokens - firsts
    if decoded <= 0:
        return None
    return 100 * model_flops(run.conf, decoded) / run.seconds \
        / run.peaks["bf16_flops_per_s"]
