"""output_tok_s: every token the host received in the window, first
tokens included, over the window's seconds.  Host clock."""


def read(ctx):
    return ctx.tokens_in(ctx.t_open, ctx.t_end) / (ctx.t_end - ctx.t_open)
