"""Share of the HBM roofline that the gather/rerank/top-k kernel reaches,
in %: the bytes its calls must move (each distinct candidate row at its
stored width, queries, weights, scales; a screen's survivors once more)
at the HBM peak, over the kernel's summed device time."""

from harness import work


def read(ctx):
    seconds = ctx["trace"].kernel_s("gather_rerank")
    if ctx["mix"]["loop"] != "closed" or ctx["spec"]["mode"] != "probe" or seconds <= 0:
        return None
    window, g = ctx["window"], ctx["geometry"]
    candidates = int(sum(int(b.counts.sum()) for b in window.batches))
    bytes_ = work.gather_rerank_bytes(candidates, window.slots, g.d, g.storage, g.keep, g.k)
    return work.roofline_pct(bytes_, seconds, ctx["device_kind"])
