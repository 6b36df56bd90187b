"""Device time per batch, in ms, of the query program's operations that are
not Pallas kernels: today the window probe (searchsorted) and the dedupe
sorts of core/index.py."""


def read(ctx):
    if ctx["mix"]["loop"] != "closed" or ctx["spec"]["mode"] != "probe":
        return None
    batches = len(ctx["window"].batches)
    return 1e3 * ctx["trace"].xla_ops_s() / batches if batches else None
