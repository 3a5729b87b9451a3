"""all_reduce_roofline: the least time of a ring all-reduce of the
shared vector (d float32) over the cell's P chips, over the measured
`all_reduce_ms` (the least chip's time under `cocoa/exchange/all_reduce`
in the traced round), in %. A ring moves 2 (P - 1) / P of the vector
through each chip's link, at the chip's inter-chip peak
(`peaks.Peak.ici_bytes`). None without a peak, with fewer than two chips
traced, or without the scope."""

SCOPE = "cocoa/exchange/all_reduce"
VALUE = 4   # bytes of a float32


def ring_bytes(d: int, chips: int) -> float:
    """Bytes through one chip's link in a ring all-reduce of d float32."""
    return 2.0 * (chips - 1) / chips * VALUE * d


def read(ctx):
    if ctx.trace is None or ctx.peak is None or len(ctx.trace.chips) < 2 \
            or ctx.trace.scope_s(SCOPE) is None:
        return None
    least = min(c.seconds_where(lambda n: SCOPE in n[3])
                for c in ctx.trace.chips)
    if least <= 0:
        return None
    need = ring_bytes(int(ctx.cell.config["data"]["d"]), ctx.cell.chips)
    return 100.0 * need / ctx.peak.ici_bytes / least
