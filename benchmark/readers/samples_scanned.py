"""Reader `samples_scanned`: the real samples the window's queries had to
read - harness.query_work's `samples`, every sample of the matched series
in (start - window, end] of each query, counted on the benchmark's own
arrays - over the window's seconds: samples scanned a second, the north
star's unit (BASELINE.json).  A count of what was ASKED over the host
clock, whatever the program fetched to answer it."""


def read(args: dict, ctx: dict):
    work = ctx["work"]()
    if not work or not ctx["window_s"]:
        return None
    return sum(w["samples"] for w in work) / ctx["window_s"]
