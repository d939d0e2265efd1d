"""Time a step's host thread waited inside its blocking reads for the
device to finish what the value depends on (the program's counter
transfers.wait_s{site=...}, raised by the one seam every designed read
goes through, before the copy), over the window's steps.  0.0 where the
window made no read through the seam; nothing where it made reads and
the program has no such counter (a program from before the seam)."""

META = {"name": "stream.read_wait_ms_per_step", "layer": "host data plane", "unit": "ms", "moves": "step_ms",
        "source": "program_counter", "better": "lower"}

COUNTER = "transfers.wait_s{site="


def read(ctx):
    w, obs = ctx["window"], ctx["obs"]
    timed = [v for k, v in obs.items() if k.startswith(COUNTER)]
    reads = sum(v for k, v in obs.items()
                if k.startswith("transfers.sanctioned{site=")
                and k.endswith("-read}"))
    if not w["steps"] or (reads and not timed):
        return None
    return 1e3 * sum(timed) / w["steps"]
