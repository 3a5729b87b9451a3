"""certificate_rmatvec_ms: device time of the instructions under the
program's `cocoa/certificate/rmatvec` scope in the traced certificate
(v = A alpha / (lambda n), apart from the primal margins and the dual
sums), mean over the chips traced, in ms per call. None where no
instruction carries the scope."""


def read(ctx):
    if ctx.trace is None:
        return None
    s = ctx.trace.scope_s("cocoa/certificate/rmatvec")
    return None if s is None or s <= 0 else 1e3 * s
