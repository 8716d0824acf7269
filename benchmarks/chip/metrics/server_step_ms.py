"""Server step: per round, the ``server_step`` span of the trainer's
close-out (unflatten and leaf uploads, the server optimizer,
``finish_round``, engine recycle); the mean over the window's
rounds."""


def read(ctx):
    xs = [s.dur_s for r in ctx.rounds if r.get("trace") is not None
          for s in r["trace"].spans_of("server_step")]
    return 1e3 * sum(xs) / len(xs) if xs else None
