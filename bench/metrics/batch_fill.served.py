"""Real queries over padded batch slots in an open-loop window, from the
client's own counts."""


def read(ctx):
    if ctx["mix"]["loop"] != "open":
        return None
    return ctx["window"].batch_fill()
