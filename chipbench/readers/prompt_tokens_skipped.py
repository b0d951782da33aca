"""Share of the window's prompt tokens that prefill did not compute: 1 minus
the growth of a prefill-token counter over the prompt tokens the generator
sent.  Both scrapes are taken with nothing in flight, so the two counts cover
the same requests."""

from chipbench import promtext


def read(ctx, series: str):
    computed = promtext.delta(ctx["before"], ctx["after"], series)
    sent = ctx["window"]["prompt_tokens_total"]
    if computed is None or not sent:
        return None
    return 100.0 * (1.0 - computed / sent)
