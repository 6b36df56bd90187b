"""Share of the HBM roofline that the exact scan kernel reaches, in %: the
whole table once per batch plus queries, weights and answers at the HBM
peak, over the kernel's summed device time. Its 3 b n d vector operations
have no published peak, so bytes bound it."""

from harness import work


def read(ctx):
    seconds = ctx["trace"].kernel_s("exact_scan")
    if ctx["spec"]["mode"] != "exact" or seconds <= 0:
        return None
    window, cfg, g = ctx["window"], ctx["config"], ctx["geometry"]
    bytes_ = work.scan_bytes(cfg["n"], g.d, window.slots, len(window.batches), g.k)
    return work.roofline_pct(bytes_, seconds, ctx["device_kind"])
