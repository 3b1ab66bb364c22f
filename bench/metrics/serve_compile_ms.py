"""Milliseconds per batch that `greedy_decode` spends lowering and
compiling its prefill and decode programs (`Decoded.compile_s`), averaged
over the window's untraced batches."""


def read(ctx, rec, t):
    b = [x for x in rec["batches"] if not x["traced"]]
    return 1e3 * sum(x["compile_s"] for x in b) / len(b) if b else None
