"""Time a step's host thread spent copying the values of its blocking
reads to NumPy once the device had them ready (the program's counter
transfers.copy_s{site=...}, raised by the one seam every designed read
goes through, after the wait), over the window's steps: packs of a few
hundred floats, so well under a millisecond.  0.0 where the window made
no read through the seam; nothing where it made reads and the program
has no such counter (a program from before the seam)."""

META = {"name": "stream.read_copy_ms_per_step", "layer": "host data plane", "unit": "ms", "moves": "step_ms",
        "source": "program_counter", "better": "lower"}

COUNTER = "transfers.copy_s{site="


def read(ctx):
    w, obs = ctx["window"], ctx["obs"]
    timed = [v for k, v in obs.items() if k.startswith(COUNTER)]
    reads = sum(v for k, v in obs.items()
                if k.startswith("transfers.sanctioned{site=")
                and k.endswith("-read}"))
    if not w["steps"] or (reads and not timed):
        return None
    return 1e3 * sum(timed) / w["steps"]
