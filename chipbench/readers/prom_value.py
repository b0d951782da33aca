"""The value of a series the server SET before the window: what the first of
the two scrapes that bracket the window (``before``) holds for the series of
that name whose labels include ``labels``, times ``scale``.  For a quantity
of the start (a gauge that is static once the server is ready, or a count of
the start's events), which no growth over the window can show.  Where
several series match, nothing is summed: that is a reading of something
else, and there is nothing to read (``None``), as where the server has no
such series."""


def read(ctx, series: str, labels: dict | None = None, scale: float = 1.0):
    want = set((labels or {}).items())
    hits = [v for (name, ls), v in ctx["before"].items() if name == series and want <= set(ls)]
    return scale * hits[0] if len(hits) == 1 else None
