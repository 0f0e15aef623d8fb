"""``span_attr_sum`` times ``scale``: a count the program records in one
unit (bytes) reported in another (MB: ``scale`` 1e-6).  Nothing where
``span_attr_sum`` reads nothing."""

from benchmark.readers import span_attr_sum


def read(run, args):
    value = span_attr_sum.read(run, args)
    return None if value is None else value * args["scale"]
