"""Block bytes checked against their stored CRCs (obs.CHECKSUMS
`bytes_verified`) per payload byte restored."""


def read(run):
    restored = sum(op.nbytes for op in run.of("restore"))
    if not restored or "bytes_verified" not in run.counters:
        return None
    return run.counters["bytes_verified"] / restored
