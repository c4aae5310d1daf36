"""Reader `trace_busy`: the share of the traced window, in percent, in
which no op ran on the device (averaged over the chips used)."""

import xtrace


def read(args: dict, ctx: dict):
    if ctx["trace"] is None:
        return None
    busy = xtrace.busy_s(ctx["trace"])
    if busy is None:
        return None
    return 100.0 * (1.0 - busy / ctx["window_s"])
