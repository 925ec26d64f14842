"""Prompt tokens the batcher fed per decode step over the window, from
its own counters (prefill runs as decode, one prompt token a row a step)."""


def read(ctx):
    c = ctx["counters"]
    return c["prefill_tokens"] / c["steps"] if c.get("steps") else None
