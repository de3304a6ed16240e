"""Coding-kernel launches (ops.KERNEL_LAUNCHES) per save in the window."""


def read(run):
    saves = len(run.of("save"))
    if not saves or "launches" not in run.counters:
        return None
    return run.counters["launches"] / saves
