"""Reader `trace_op_ms`: device milliseconds of the executed HLO ops whose
own name (xtrace.op_name: "%all-reduce.2 = ..." -> "all-reduce.2") matches
`pattern`, averaged over the device planes that ran any op, per query of
the traced window.  Returns nothing where the trace has no device op or no
op of that name (one chip runs no collective)."""

import re

import xtrace


def read(args: dict, ctx: dict):
    if ctx["trace"] is None or not ctx["queries"]:
        return None
    rx = re.compile(args["pattern"])
    planes = [ev for ev in xtrace.device_lines(ctx["trace"], xtrace.OPS_LINE)
              if ev]
    matched = [dur for ev in planes for text, _, dur in ev
               if rx.search(xtrace.op_name(text))]
    if not matched:
        return None
    return sum(matched) / len(planes) / 1e6 / ctx["queries"]
