"""Of the device's idle time in gaps a host phase can explain, the share that
lies inside the program's ``engine.*`` annotations
(``DeviceTrace.idle_named_share``).  Nothing to read without a trace, without
annotations on a clock shown to be the device's, or where the gaps hold under
a millisecond together."""


def read(ctx):
    trace = ctx["trace"]
    return None if trace is None else trace.idle_named_share()
