"""Coding-kernel launches (ops.KERNEL_LAUNCHES) per restore in the window."""


def read(run):
    restores = len(run.of("restore"))
    if not restores or "launches" not in run.counters:
        return None
    return run.counters["launches"] / restores
