"""volume engine (EC read path): of the window's answered reads of pool
keys, the share whose record has bytes on a lost data shard — by the
harness's own layout (`pool_reads_on_lost_shards` over `pool_reads`),
never a counter of the program's."""


def read(facts):
    req = facts["requests"]
    if not req or not req.get("pool_reads"):
        return None
    return 100.0 * req["pool_reads_on_lost_shards"] / req["pool_reads"]
