"""Share of the traced window in which the device ran nothing while the
engine was in ``table_sync`` or ``dispatch``: the block-table upload, the
operand uploads and the launch. The four ``idle_in_*`` add up to
``device_idle_pct`` of the same run."""
from benchmark import phases


def read(run):
    shares = phases.idle_shares(run)
    return shares[phases.DISPATCH] if shares else None
