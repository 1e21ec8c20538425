"""Share of the device's BUSY time spent inside the jitted programs whose
module names match (the trace's module line: a program's whole execution,
its ops' gaps included). args: {"patterns": [regex]}. Percent. None where
the trace holds no such program."""


def read(data, args):
    tr = data.get("trace_summary")
    if tr is None or not tr.busy_s:
        return None
    seconds = tr.module_time(args["patterns"])
    return 100.0 * seconds / tr.busy_s if seconds else None
