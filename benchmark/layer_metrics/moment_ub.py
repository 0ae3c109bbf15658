"""Median over the counted jobs of one component of a list in the result
(``args``: ``field``, a dotted path to the list, and ``component``): the
cell's total moment. harness/sources.result_field reads numbers only."""

from benchmark.harness import sources


def read(record, args):
    lists = [sources._dig(j["result"], args["field"])
             for j in sources._good(record)]
    return sources._median(
        v[int(args["component"])] for v in lists if v is not None)
