"""Set-up that lies outside the program: the time from the process's own
start to the window's opening, less what the program's start-up record
accounts for (``setup_import_s``, ``setup_engine_s``, ``setup_programs_s``,
``setup_serve_s`` and the ``startup:tables`` spans). What is left is the
harness's: the backend's client where the harness starts it, the adapter's
and the generator's own work. It is the guard on the other four, as
``tick_unscoped_pct`` is on the parts of a tick.

``parts`` is the one reading of the record the set-up metrics share: their
readers find this file by name through the cell's spec. A program without
the record (a parent commit) gives nothing."""


def parts(run):
    """The start-up record of ``run.report`` cut at ``run.t_open``, as the
    set-up metrics want it, or None without a record."""
    startup = (run.report or {}).get("startup")
    if not startup:
        return None
    t_open, t_end = run.t_open, run.t_end

    def before(phase):
        # Spans of ``phase`` that began before the window, an open one (the
        # window's own serve() call) and one that runs on cut at its
        # opening: (start, end, fields, whether it closed before it).
        return [(t0, t_open if t1 is None else min(t1, t_open), fields,
                 t1 is not None and t1 <= t_open)
                for name, t0, t1, fields in startup["spans"]
                if name == "startup:" + phase and t0 < t_open]

    def seconds(*phases):
        return sum(t1 - t0 for p in phases for t0, t1, _, _ in before(p))

    built = [f for _, _, f, closed in before("program") if closed]
    out = {
        "import": seconds("import", "backend"),
        "engine": seconds("params", "engine"),
        "programs": sum(f["trace_s"] + f["lower_s"] + f["compile_s"]
                        + f["fetch_s"] for f in built),
        "built": len(built),
        "from_cache": sum(1 for f in built if f["from_cache"]),
        "tables": seconds("tables"),
        "in_window": sum(1 for name, t0, _, _ in startup["spans"]
                         if name == "startup:program"
                         and t_open <= t0 < t_end),
    }
    out["serve"] = seconds("serve") - out["programs"] - out["tables"]
    out["unseen"] = (t_open - startup["t_process"]) - out["import"] \
        - out["engine"] - out["programs"] - out["serve"] - out["tables"]
    return out


def read(run):
    p = parts(run)
    return None if p is None else p["unseen"]
