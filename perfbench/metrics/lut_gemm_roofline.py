"""Least time of the fused LUT-GEMM calls (W4A4 and W8A4), from their
shapes, over their device time in the trace."""

from perfbench import work
from perfbench.discover import resolve_bits
from perfbench.readers import LUT_GEMM, share


def read(run):
    cfg, q = run.cfg, run.cfg["quant"]

    def least(_, pos):
        m = pos.size
        return cfg["n_layers"] * sum(
            work.least_seconds(*work.lut_gemm(m, k, n, resolve_bits(q, "blocks/" + name),
                                              q["a_bits"]), run.peaks)
            for name, k, n in work.projections(cfg))
    return share(run, LUT_GEMM, least)
