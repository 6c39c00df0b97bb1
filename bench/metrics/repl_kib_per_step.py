"""Replication: KiB the transport shipped to replicas per engine step in
the window (the TransportChannel's "repl" tally, a count)."""


def read(run):
    if not run.steps:
        return None
    return run.repl_bytes / run.steps / 1024
