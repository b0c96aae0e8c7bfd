"""Peak pool blocks in use over the pool's blocks, from ``ServeReport.kv``."""


def read(run):
    kv = run.report.get("kv") or {}
    if not kv.get("pool_blocks"):
        return None
    return 100.0 * kv["peak_blocks_used"] / kv["pool_blocks"]
