"""Kernels: the paged attention kernel's share of its roofline over the
traced decode steps: the least time its work needs at the chip's peaks
(operations and bytes from the live slots' context lengths, carried on the
benchmark's decode-step spans) over the kernel's device time inside those
steps."""
from bench.roofline import least_seconds


def is_kernel(e) -> bool:
    return "paged_attention" in (e.name + " " + str(e.stats.get("hlo_op",
                                                                 "")))


def read(run):
    tr = run.trace
    if tr is None:
        return None
    least = kernel = 0.0
    for s in tr.spans_named("decode_step"):
        if not s.stats.get("slots"):
            continue
        t = tr.ops_within(s.start, s.end, is_kernel)
        if t <= 0:
            continue
        kernel += t
        least += least_seconds(float(s.stats["attn_flops"]),
                               float(s.stats["attn_bytes"]), run.peaks)
    return 100 * least / kernel if kernel > 0 else None
