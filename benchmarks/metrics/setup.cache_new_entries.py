"""Entries the run added to the persistent compile cache: settles at a
small constant once the cell has run in a checkout."""

META = {"name": "setup.cache_new_entries", "layer": "entry points, device selection", "unit": "count", "moves": "setup_s",
        "source": "program_counter", "better": "lower"}


def read(ctx):
    return float(ctx["cache_new_entries"])
