"""Least time of the paged-attention calls, from each row's real
context capped by each layer's window, over their device time in the trace."""

from collections import Counter

from perfbench import work
from perfbench.readers import PAGED_ATTN, share


def read(run):
    cfg, serve = run.cfg, run.serve

    def least(slots, pos):
        return sum(n * work.least_seconds(*work.paged_attention(
            slots, pos, heads=cfg["n_heads"], kv_heads=cfg["n_kv_heads"],
            head_dim=cfg["head_dim"], block_size=serve["block_size"],
            window=w, kv_bits=cfg["quant"]["kv_bits"]), run.peaks)
            for w, n in Counter(work.layer_windows(cfg)).items())
    return share(run, PAGED_ATTN, least)
