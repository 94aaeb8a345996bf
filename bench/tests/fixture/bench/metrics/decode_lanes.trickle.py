"""A metric that only the fixture defines: decode lanes per call."""


def read(run):
    c = run.counts
    return c["decode_lanes"] / c["decode_calls"] if c["decode_calls"] else None
