"""The fused superstep kernel's share of its roofline, in %: the least
time the chip needs for the work the schedule asked of it (the larger of
bytes / HBM bandwidth and flops / peak, `bench.work`) over the kernel's
device time in the traced window."""

from bench import work


def read(run):
    c, s = run.recorder.counters, run.shapes
    if run.trace is None or not c.get("supersteps"):
        return None
    t = work.kernel_seconds(run.trace["ops"])
    if t <= 0:
        return None
    nbytes = work.kernel_bytes(
        c["tile_pair_loads"], c["supersteps"], jobs=s["jobs"],
        num_blocks=s["num_blocks"], block=s["block"], semiring=s["semiring"])
    flops = work.kernel_flops(c["tile_pair_loads"], jobs=s["jobs"],
                              block=s["block"], semiring=s["semiring"])
    least, _ = work.roofline_seconds(nbytes, flops, run.peaks)
    return 100.0 * least / t
