"""all_reduce_ms: device time of the instructions under the program's
`cocoa/exchange/all_reduce` scope (the cross-chip sum of the workers'
vectors on a mesh) in the traced round, on the traced chip where it is
least, in ms per round. Every chip waits there for the slowest one; the
chip that arrived last waited least, so its time is the transfer. None
with fewer than two chips traced or without the scope: a one-chip cell
issues no collective."""

SCOPE = "cocoa/exchange/all_reduce"


def read(ctx):
    if ctx.trace is None or len(ctx.trace.chips) < 2 \
            or ctx.trace.scope_s(SCOPE) is None:
        return None
    least = min(c.seconds_where(lambda n: SCOPE in n[3])
                for c in ctx.trace.chips)
    return 1e3 * least if least > 0 else None
