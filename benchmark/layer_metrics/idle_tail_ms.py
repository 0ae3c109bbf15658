"""Device-idle ms charged to the named spans and below, per traced
iteration (harness/idle.py)."""

from benchmark.harness import idle


def read(record, args):
    return idle.idle_ms_per_iteration(record, args)
