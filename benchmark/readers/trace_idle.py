"""Share of the traced span in which no op ran on the device: 1 - the union
of the leaf-op intervals / the span from the first op's start to the last
op's end, both on the device's clock, averaged over the chips. Percent."""


def read(data, args):
    tr = data.get("trace_summary")
    if tr is None or not tr.span_s:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.span_s)
