"""Least time of the paged-attention calls, from each row's real
context, over their device time in the trace."""

from perfbench import work
from perfbench.readers import PAGED_ATTN, share


def read(run):
    cfg, serve = run.cfg, run.serve

    def least(slots, pos):
        return cfg["n_layers"] * work.least_seconds(*work.paged_attention(
            slots, pos, heads=cfg["n_heads"], kv_heads=cfg["n_kv_heads"],
            head_dim=cfg["head_dim"], block_size=serve["block_size"],
            window=cfg.get("sliding_window", 0), kv_bits=cfg["quant"]["kv_bits"]),
            run.peaks)
    return share(run, PAGED_ATTN, least)
