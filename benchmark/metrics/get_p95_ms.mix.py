"""get_p95_ms.mix: 95th percentile latency of every GET of the mix in the
window (host clock, nearest rank; a failed GET counts as slower than any
that completed). Cell C runs at capacity, so this tail is a per-layer
reading beside its throughput; the sample count is on stderr."""

from lib import readers


def read(ctx):
    return readers.percentile_ms(ctx, "get", 0.95)
