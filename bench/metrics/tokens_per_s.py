"""Output tokens emitted in the window over the window's length."""


def read(ctx):
    tokens = sum(c.report.total_tokens for c in ctx.calls)
    return tokens / ctx.window_s
