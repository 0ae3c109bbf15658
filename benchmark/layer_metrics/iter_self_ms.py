"""Per job, the self time of its spans of one name (the span less the union
of its children), median or sum over the job's spans; median over jobs
(harness/idle.py)."""

from benchmark.harness import idle


def read(record, args):
    return idle.self_ms(record, args)
