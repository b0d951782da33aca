"""What the generator's clock sees of TTFT and the server's does not: the
mean of the generator's TTFTs minus the mean of the server's own (its
arrival of the request to its first stream event written), the latter from a
sum and a count on ``/metrics`` (``prom_mean_delta``).  The populations
differ slightly: requests completed inside the window against requests
finished between the two scrapes."""

from chipbench.readers import prom_mean_delta


def read(ctx, sum_series: str, count_series: str, labels: dict | None = None):
    ttfts = ctx["window"]["ttft_s"]
    server_s = prom_mean_delta.read(ctx, sum_series, count_series, labels)
    if not ttfts or server_s is None:
        return None
    return 1000.0 * (sum(ttfts) / len(ttfts) - server_s)
