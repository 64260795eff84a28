"""Least time of the Orizuru outlier-detection calls, from their shapes,
over their device time in the trace. Detection is needed once per
distinct activation tensor (``work.detected_widths``): the attention
input (shared by wq, wk, wv), and wo's, wi's and wd's inputs."""

from perfbench import work
from perfbench.readers import ORIZURU, share


def read(run):
    cfg, q = run.cfg, run.cfg["quant"]
    widths = work.detected_widths(cfg)

    def least(_, pos):
        m = pos.size
        return cfg["n_layers"] * sum(
            work.least_seconds(*work.orizuru(m, n, work.outliers_per_side(n, q["outlier_frac"])),
                               run.peaks)
            for n in widths)
    return share(run, ORIZURU, least)
