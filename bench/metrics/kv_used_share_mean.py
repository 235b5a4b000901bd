"""Mean share of the KV pool's tokens in use after each interval of the
window (`BlockManager.physical_used_tokens` over the pool's tokens)."""


def read(rec):
    s = rec.window_steps()
    if not s or not rec.pool_tokens:
        return None
    return 100.0 * sum(x.kv_used_tokens for x in s) / len(s) / rec.pool_tokens
