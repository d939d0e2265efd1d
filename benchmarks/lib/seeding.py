"""Inputs from ``--seed``: each body of the configuration is moved by a
fraction of the finest cell and its tail beat is given a phase, both
drawn from the seed within the ranges the configuration file states.
The compiled shapes do not depend on either."""

from __future__ import annotations

import numpy as np


def body_lines(config: dict, seed: int):
    rng = np.random.default_rng(int(seed))
    rules = config["seed"]
    h = float(rules["finest_h"])
    lines = []
    for body in config["bodies"]:
        off = rng.uniform(-1.0, 1.0, 3) * float(rules["offset_cells"]) * h
        lo, hi = rules["phase"]
        phi = rng.uniform(lo, hi)
        pos = np.asarray(body["pos"], np.float64) + off
        lines.append(body["line"].format(
            x=repr(float(pos[0])), y=repr(float(pos[1])),
            z=repr(float(pos[2])), phi=repr(float(phi))))
    return lines


def build_argv(config: dict, traffic: dict, seed: int, workdir: str):
    """The command line ``python -m cup3d_tpu`` would be given: the
    configuration's flags, the traffic's driver mode, the seeded bodies."""
    argv = list(config["argv"])
    for key, value in traffic["flags"].items():
        argv += ["-" + key, str(value)]
    # the step budget is stated at build (a run without one cannot take
    # the scan megaloop); the harness raises it as the run goes on
    argv += ["-nsteps", str(traffic["warmup_steps"])]
    if config["bodies"]:
        argv += ["-factory-content", "\n".join(body_lines(config, seed))]
    return argv + ["-path4serialization", workdir]
