"""Share of the large-set calls (host dispatch to the end of the wait) in
which no operation ran on the device, averaged over the devices."""


def read(ctx):
    spans = ctx["reader"]("collective_share").large_spans(ctx)
    total = sum(e - s for s, e in spans) * 1e-9
    if total <= 0 or not ctx["trace"]["devices"]:
        return None
    busy_s = ctx["reduce"].busy_within(ctx["trace"], spans)
    return 100.0 * (1.0 - busy_s / total)
