"""all_reduce_wait_ms: the mean over the traced chips of the device time
under `cocoa/exchange/all_reduce` in the traced round, less its least
(`all_reduce_ms`), in ms per round: the time chips wait at the
all-reduce for the slowest chip's local solve. None with fewer than two
chips traced or without the scope."""

SCOPE = "cocoa/exchange/all_reduce"


def read(ctx):
    if ctx.trace is None or len(ctx.trace.chips) < 2 \
            or ctx.trace.scope_s(SCOPE) is None:
        return None
    per_chip = [c.seconds_where(lambda n: SCOPE in n[3])
                for c in ctx.trace.chips]
    return 1e3 * (sum(per_chip) / len(per_chip) - min(per_chip))
