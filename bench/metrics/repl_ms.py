"""Replication: per engine step in the trace, the host time of the
benchmark's spans around ``RealEngine._replicate`` and
``RealEngine.flush_replication``, plus the device time of the block-copy
programs they launch (``_copy_blocks``, ``_copy_blobs``)."""


def is_copy(e) -> bool:
    return "copy_blocks" in e.name or "copy_blobs" in e.name


def read(run):
    tr = run.trace
    if tr is None or not any(tr.modules):
        return None
    steps = tr.spans_named("engine_step")
    if not steps:
        return None
    host = sum(s.end - s.start for name in ("replicate", "flush_replication")
               for s in tr.spans_named(name)) / 1e9
    return (host + tr.module_seconds(is_copy)) / len(steps) * 1e3
