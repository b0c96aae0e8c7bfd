"""Chunk summary rows the EVA layers wrote over those that were due, in the
window's decode ticks: the flight records' ``eva_summaries_written`` (counted
on the device, on the tick's one fetch) over ``eva_summaries_due`` (the chunks
the packed rows closed x the layers, counted on the host). 100 for a sound
program (every closed chunk's row in every layer, once); over it for one that
rewrites, under it for one that skips. None where the records carry no such
field (a program without EVA layers) or nothing was due."""


def read(run):
    recs = [r for r in run.flight or ()
            if run.t_open <= r.get("t_s", -1.0) < run.t_end
            and "eva_summaries_written" in r and not r.get("chunk_tokens")
            and r.get("occupancy")]
    due = sum(r.get("eva_summaries_due", 0) for r in recs)
    if not due:
        return None
    return 100.0 * sum(r["eva_summaries_written"] for r in recs) / due
