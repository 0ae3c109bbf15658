"""Device milliseconds a traced iteration spends in the fused step's
two-channel XC branch (scope step_xc/xc_spin). Read by
layer_metrics/hpsi_device_share.py's reader with this metric's ``args``."""

import os

from benchmark.harness import sources


def read(record, args):
    return sources.python(record, args, path=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "hpsi_device_share.py"))
