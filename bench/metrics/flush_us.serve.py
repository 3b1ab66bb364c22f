"""Microseconds per call of the shadow-dispatching runtime's `flush` and
`drain` inside `greedy_decode`, on the host clock, over the window's
untraced batches."""


def read(ctx, rec, t):
    b = [x for x in rec["batches"] if not x["traced"]]
    calls = sum(x["flush_calls"] for x in b)
    return 1e6 * sum(x["flush_s"] for x in b) / calls if calls else None
