"""A ratio of two quantities the program records as span attributes: the
sum of the attributes ``num`` over the sum of ``den``, both over every span
named ``spans`` in the window's jobs (so a job with several such spans
weighs each by its denominator).  ``counter_share`` as a plain ratio, not
a percentage.  Nothing when the denominator is zero: the tracer was off, no
such span ran (one device, no exchange), or the program does not record
the attributes."""

from benchmark.readers import counter_share


def read(run, args):
    share = counter_share.read(run, args)
    return None if share is None else share / 100.0
