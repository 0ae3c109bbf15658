"""Device milliseconds a traced iteration spends in the subspace
eigensolver's kernel half (scope davidson_rr/eigh_kernel). Read by
layer_metrics/eigh_reduce_ms.py's reader with this metric's ``args``."""

import os

from benchmark.harness import sources


def read(record, args):
    return sources.python(record, args, path=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "eigh_reduce_ms.py"))
