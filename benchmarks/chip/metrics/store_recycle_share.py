"""Object store: the share of the window's rounds' store puts that the
in-proc store served from its free list (a recycled buffer), in
percent.  Reads each round's ``store_puts`` and ``store_recycled``
counters; returns nothing where the program's round outcomes carry no
such counters."""


def read(ctx):
    outs = [r["outcome"] for r in ctx.rounds]
    if not outs or not all(hasattr(o, "store_recycled") for o in outs):
        return None
    puts = sum(int(o.store_puts) for o in outs)
    recycled = sum(int(o.store_recycled) for o in outs)
    return 100.0 * recycled / puts if puts else None
