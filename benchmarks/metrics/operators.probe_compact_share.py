"""Share of the window's force rows whose surface band fitted the slot
budget of the probe that produced it (the program's counter
operators.probe_compacted, raised where the host stores a row), and was
not cut to its largest cells (operators.probe_truncated): 100 while every
budget holds its band, and less before a truncated band shows in the
forces.  Nothing where the program has neither counter, or no row of a
probe was stored."""

META = {"name": "operators.probe_compact_share", "layer": "operators", "unit": "%", "moves": "step_ms",
        "source": "program_counter", "better": "higher"}


def read(ctx):
    obs = ctx["obs"]
    compacted = obs.get("operators.probe_compacted", 0)
    rows = compacted + obs.get("operators.probe_truncated", 0)
    return 100.0 * compacted / rows if rows else None
