"""step_mfu: the whole served step's share of the cell's chips' int8
peak: ops per packet (counted from the model's shapes) times the traced
run's ``throughput_pps``, over the cell's chips times one chip's peak,
in %."""


def read(ctx):
    tput = ctx.e2e.get("throughput_pps")
    if tput is None or ctx.peaks is None:
        return None
    peak = ctx.workload["chips"] * ctx.peaks["int8_ops_per_s"]
    return 100.0 * ctx.work.ops_per_packet(ctx.cfg) * tput / peak
