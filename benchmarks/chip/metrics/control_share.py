"""Round loop: the control tier's share of round wall time, summed over
the window's rounds (``RoundTrace.breakdown()``: spawn, loop glue, fold
orchestration, the close-out release)."""


def read(ctx):
    parts = [r["trace"].breakdown() for r in ctx.rounds
             if r.get("trace") is not None]
    wall = sum(b["wall_s"] for b in parts)
    if not parts or wall <= 0:
        return None
    return 100.0 * sum(b["control_s"] for b in parts) / wall
