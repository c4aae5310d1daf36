"""Reader `client_percentile`: a percentile, in ms, of the window's
client-side latencies of the queries whose template matches `pattern` (a
regular expression searched in the template's text as the mix gives it).
Returns nothing where the mix asks no such template."""

import re

import stats


def read(args: dict, ctx: dict):
    lats = [lat for tmpl, ls in ctx["by_template"].items()
            if re.search(args["pattern"], tmpl) for lat in ls]
    if not lats:
        return None
    return 1e3 * stats.percentile(lats, args["percentile"])
