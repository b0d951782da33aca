"""Share of a labelled counter's growth that one label value took: growth of
the series under ``labels`` over growth of the series under every label,
between the two scrapes that bracket the window, times ``scale``.  Nothing to
read (``None``) where the server has no such series or it did not grow."""

from chipbench import promtext


def read(ctx, series: str, labels: dict, scale: float = 100.0):
    total = promtext.delta(ctx["before"], ctx["after"], series)
    part = promtext.delta(ctx["before"], ctx["after"], series, labels)
    if not total or total < 0 or part is None:
        return None
    return scale * part / total
