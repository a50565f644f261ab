"""tokens_per_s: workers x batch x seq x steps completed in the window,
over the window's seconds on the host clock (all the cell's chips)."""


def read(ctx):
    if not ctx.window_steps:
        return None
    return ctx.window_steps * ctx.tokens_per_step / ctx.window_s
