"""solve_host_ms: the solver's own host time per round: the `host_s` of
the window's `RoundRecord`s (time inside the program's `cocoa_solve`
span outside its lowering, compile, round, certificate and hook spans:
preparation, placement, record building and the loop's own lines),
summed and divided by the rounds the records cover, the traced round's
record left out. In ms per round; None where the records carry no
`host_s`."""


def read(ctx):
    secs = rounds = 0.0
    for sv in ctx.solves:
        for r in sv.records:
            if sv.traced_round is not None and r.round == sv.traced_round:
                continue
            host_s = getattr(r, "host_s", None)
            if host_s is None:
                return None
            secs += host_s
            rounds += r.rounds_in_record
    if rounds == 0:
        return None
    return 1e3 * secs / rounds
