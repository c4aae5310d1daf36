"""Reader `roofline`: the least time the chip could take for the work the
traced window's queries needed, over the device time of the programs that
did it, in percent.

least_bytes is the yardstick: what a query NEEDS moved through HBM,
whatever implements it - every real sample of the matched series in the
fetched range once (float32 value + int32 timestamp, 8 B) plus the answer
written once (float32 per series row and grid step).  The rollups are
compares and adds on the VPU, for which the chip publishes no peak, so the
bound is HBM bandwidth by construction.
"""

import xtrace

SAMPLE_BYTES = 8
OUT_BYTES = 4


def least_bytes(samples: int, out_values: int) -> int:
    return samples * SAMPLE_BYTES + out_values * OUT_BYTES


def read(args: dict, ctx: dict):
    if ctx["trace"] is None:
        return None
    launches = xtrace.programs(ctx["trace"], args["pattern"])
    device_s = sum(e[2] for e in launches) / 1e9
    if not device_s:
        return None
    least_s = sum(least_bytes(w["samples"], w["out_values"])
                  for w in ctx["work"]()) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / device_s
