"""Rows that go on above the seam over the rows below it, in the window's
MIXED ticks: 100 x the sum of the flight records' ``rows_cross`` (rows the
layers above the shared full-attention layer computed) over the sum of
``rows_self`` (rows the layers up to it computed). A packed tick of ``C``
chunk members of ``Tq`` rows beside ``S`` slots reads ``100 S / (C Tq + S)``
(15.8 at 48 slots and one chunk of 256); a program that runs every row
through every layer reads 100. Lower is the convention: fewer rows above the
seam. None where the records carry no such fields (a model that cuts no
rows, or a parent commit)."""


def read(run):
    recs = [r for r in run.flight or ()
            if run.t_open <= r.get("t_s", -1.0) < run.t_end
            and r.get("chunk_tokens") and r.get("rows_self")]
    if not recs:
        return None
    return 100.0 * sum(r["rows_cross"] for r in recs) \
        / sum(r["rows_self"] for r in recs)
