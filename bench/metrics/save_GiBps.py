"""Checkpoint saves: payload GiB of every save started in the window over
the time from the window's start to the last save's completion."""
from bench.harness.record import rate_GiBps


def read(run):
    return rate_GiBps(run.of("save"))
