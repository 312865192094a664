"""KV block pool (serve/blockpool.py): the pool's blocks reserved by live
requests, as a share of its usable blocks, over the window's ticks (%)."""


def read(run):
    ticks = run.window_ticks()
    if run.pool is None or not ticks:
        return None
    return 100.0 * sum(t.kv_blocks for t in ticks) / (len(ticks) * run.pool[0])
