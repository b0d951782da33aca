"""A percentile of the device time (milliseconds) of one jitted program: of
the module events whose name matches ``pattern`` (nearest rank)."""

import re

from chipbench import stats


def read(ctx, pattern: str, q: float):
    trace = ctx["trace"]
    if trace is None:
        return None
    rx = re.compile(pattern)
    ms = [dur / 1e6 for name, _, dur in trace.all_modules() if rx.search(name)]
    if not ms:
        return None
    return stats.percentile(ms, q, min_beyond=0)
