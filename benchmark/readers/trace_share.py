"""Share of the device's BUSY time spent in ops whose names match (self
time: a container's body is not counted twice). args: {"patterns": [...]}.
Percent."""


def read(data, args):
    tr = data.get("trace_summary")
    if tr is None or not tr.busy_s:
        return None
    return 100.0 * tr.op_time(args["patterns"]) / tr.busy_s
