"""``plan`` + ``pack`` + ``table_sync`` + ``dispatch`` of a tick: from the
chunk plan through the NumPy operands, the block-table upload and the
operand uploads to the tick program's launch returning; median over the
window's fetched ticks that end before the profiler starts."""
from benchmark import phases


def read(run):
    return phases.percentile_ms(
        run, ("plan", "pack", "table_sync", "dispatch"), 0.5)
