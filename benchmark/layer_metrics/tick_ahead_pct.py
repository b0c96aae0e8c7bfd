"""Share of the window's fetched ticks whose program went out before the
host fetched the program before it: the flight records' ``ahead`` over the
records that have a ``fetch`` phase. 100 less it is the share of ticks whose
plan needed token values on the host (``sync_reason`` says which: a
speculative verify, a token tree, a fork family, staged int8 prefill, whole
admission) or that found nothing in flight. A program without the field (a
parent commit) gives nothing."""


def read(run):
    recs = [r for r in run.flight or ()
            if run.t_open <= r.get("t_s", -1.0) < run.t_end
            and any(p[0] == "fetch" for p in r.get("phases") or ())]
    if not any("ahead" in r for r in recs):
        return None
    return 100.0 * sum(1 for r in recs if r.get("ahead")) / len(recs)
