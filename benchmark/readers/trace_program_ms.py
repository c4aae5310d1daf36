"""Reader `trace_program_ms`: device milliseconds of the launched programs
whose name matches `pattern`, per query of the traced window."""

import xtrace


def read(args: dict, ctx: dict):
    if ctx["trace"] is None or not ctx["queries"]:
        return None
    launches = xtrace.programs(ctx["trace"], args["pattern"])
    if not launches:
        return None
    return sum(e[2] for e in launches) / 1e6 / ctx["queries"]
