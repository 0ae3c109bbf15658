"""Share of the capture's device busy time in the subspace eigensolver's operations
of the batched k-set solve, found by name in the trace (harness/name_share.py)."""

from benchmark.harness import name_share


def read(record, args):
    return name_share.read(record, args)
