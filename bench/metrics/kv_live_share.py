"""KV block pool (serve/blockpool.py): the KV positions live requests have
written, as a share of the positions their reserved blocks hold, over the
window's ticks (%). A request reserves blocks for its prompt and whole
output at admission, so the rest is memory reserved and not yet used."""


def read(run):
    ticks = run.window_ticks()
    reserved = sum(t.kv_blocks for t in ticks)
    if run.pool is None or not reserved:
        return None
    return 100.0 * sum(t.kv_tokens for t in ticks) / (reserved * run.pool[1])
