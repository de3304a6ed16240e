"""Bytes the block store handed out (TrafficStats inner + cross) per
payload byte restored."""


def read(run):
    restored = sum(op.nbytes for op in run.of("restore"))
    if not restored or "store_bytes" not in run.counters:
        return None
    return run.counters["store_bytes"] / restored
