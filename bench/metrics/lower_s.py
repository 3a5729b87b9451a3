"""lower_s: the part of `compile_s` spent tracing and lowering the round
and the certificate in the warm-up (the program's `cocoa_lower` spans,
as its `RoundRecord`s report them, host clock). `compile_s - lower_s` is
the backend compile or the compile-cache load. None where the records
carry no `lower_s`."""


def read(ctx):
    if not ctx.warm_records:
        return None
    parts = [getattr(r, "lower_s", None) for r in ctx.warm_records]
    if any(p is None for p in parts):
        return None
    return sum(parts)
