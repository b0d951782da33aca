"""Observations of a histogram the server keeps that took LONGER than one of
its bucket bounds: growth of ``_count`` less growth of ``_bucket{le=}``
between the two scrapes that bracket the window, summed over the values of
``label`` that do not start with ``skip_prefix``.  A count of the whole
window, traced or not.  Nothing to read (``None``) where the server has no
such histogram."""

from chipbench import promtext


def read(ctx, series: str, le: str, label: str, skip_prefix: str = ""):
    before, after = ctx["before"], ctx["after"]
    over = None
    for name, labels in after:
        value = dict(labels).get(label)
        if name != series + "_count" or value is None:
            continue
        if skip_prefix and value.startswith(skip_prefix):
            continue
        n = promtext.delta(before, after, name, {label: value})
        under = promtext.delta(before, after, series + "_bucket", {label: value, "le": le})
        if under is not None:
            over = (over or 0.0) + n - under
    return over
