"""Share of the capture's device busy time in the band solve's local operator
(scope davidson_hpsi/local_op). Read by
layer_metrics/hpsi_device_share.py's reader with this metric's ``args``."""

import os

from benchmark.harness import sources


def read(record, args):
    return sources.python(record, args, path=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "hpsi_device_share.py"))
