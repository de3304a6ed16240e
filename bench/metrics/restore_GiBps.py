"""Resumes: payload GiB of every restore started in the window (restore,
device_put of the leaves, block_until_ready) over the time from the
window's start to the last completion."""
from bench.harness.record import rate_GiBps


def read(run):
    return rate_GiBps(run.of("restore"))
