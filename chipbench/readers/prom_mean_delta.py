"""Mean of a quantity the server keeps as a pair of counters, a sum and a
count: growth of the sum over growth of the count between the two scrapes
that bracket the window.  Nothing to read (``None``) where the server has no
such series or the count did not grow."""

from chipbench import promtext


def read(ctx, sum_series: str, count_series: str, labels: dict | None = None,
         scale: float = 1.0):
    n = promtext.delta(ctx["before"], ctx["after"], count_series, labels)
    total = promtext.delta(ctx["before"], ctx["after"], sum_series, labels)
    if not n or n < 0 or total is None:
        return None
    return scale * total / n
