"""CLI entry point: ``python -m cup3d_tpu -bpdx ... -factory-content ...``.

The reference's ``main()`` (main.cpp:15982-15994): parse flags, build the
driver, ``init()``, ``simulate()``.  Reference-style flag grammar
(``-key value...``, ``+key`` append, first occurrence wins) is
config.parse_args; ``-conf FILE`` pulls extra flags from a config file
with ``#`` comments (ArgumentParser file mode, main.cpp:10243-10287) at
lower precedence than the command line; ``-factory FILE`` appends obstacle
lines to ``-factory-content`` (ObstacleFactory, main.cpp:13247-13267).

Driver selection is capability-based: ``levelMax > 1`` runs the adaptive
block forest (AMRSimulation), ``levelMax == 1`` the dense uniform-grid
driver with the spectral or iterative Poisson solver per
``-poissonSolver``.  The parsed config is recorded to
``argumentparser.log`` (main.cpp:10226-10240).
"""

from __future__ import annotations

import dataclasses
import os
import sys
from typing import List, Optional

from cup3d_tpu.config import parse_args, parse_config_file, parse_factory


def _expand_conf(argv: List[str]) -> List[str]:
    """Splice ``-conf FILE`` flags out, appending the file's tokens after
    the command line (CLI tokens keep precedence: first occurrence wins)."""
    out: List[str] = []
    tail: List[str] = []
    i = 0
    while i < len(argv):
        if argv[i] == "-conf":
            if i + 1 >= len(argv):
                raise ValueError("-conf needs a file path")
            with open(argv[i + 1]) as f:
                tail.extend(parse_config_file(f.read()))
            i += 2
        else:
            out.append(argv[i])
            i += 1
    return out + tail


def build_driver(argv: List[str]):
    cfg = parse_args(_expand_conf(argv))
    multi_obstacle = (
        len(parse_factory(cfg.resolved_factory_content() or "")) > 1
    )
    # capability-based: levelMax>1 needs the forest; pipelined
    # multi-obstacle runs also route to the forest driver (its vmapped
    # device megastep handles many bodies; the uniform driver's fast
    # path is single-obstacle) — at levelMax=1 the forest IS the
    # uniform grid, just block-laid-out
    if cfg.levelMax > 1 or (cfg.pipelined and multi_obstacle):
        from cup3d_tpu.sim.amr import AMRSimulation

        return AMRSimulation(cfg)
    from cup3d_tpu.sim.simulation import Simulation

    return Simulation(cfg)


def _log_config(driver) -> None:
    cfg = driver.cfg
    os.makedirs(cfg.path4serialization or ".", exist_ok=True)
    path = os.path.join(cfg.path4serialization, "argumentparser.log")
    with open(path, "w") as f:
        for field in dataclasses.fields(cfg):
            f.write(f"{field.name} {getattr(cfg, field.name)!r}\n")


def main(argv: Optional[List[str]] = None):
    """Run one case to its end and return the finished driver (in-process
    callers such as ``chip_smoke.py`` inspect its state; the command
    line ignores it)."""
    args = sys.argv[1:] if argv is None else argv
    if args and args[0] == "fleet":
        # many-simulation serving mode: `python -m cup3d_tpu fleet
        # --scenarios spec.json` drains a multi-tenant scenario queue
        # (fleet/server.py) and prints the per-tenant summary JSON
        from cup3d_tpu.fleet.cli import main as fleet_main

        raise SystemExit(fleet_main(args[1:]))
    if args and args[0] == "aot":
        # persistent-executable-store operations: `python -m cup3d_tpu
        # aot warm|list|gc|verify|probe` (aot/cli.py) manage the
        # zero-cold-start store and measure boot-to-first-dispatch
        from cup3d_tpu.aot.cli import main as aot_main

        raise SystemExit(aot_main(args[1:]))
    # the solver path keeps XLA's persistent compile cache at a fixed
    # place (the fleet/aot subcommands above manage their own
    # executable store, aot/store.py)
    from cup3d_tpu.utils import compile_cache

    compile_cache.enable()
    driver = build_driver(args)
    _log_config(driver)
    driver.init()
    driver.simulate()
    return driver


if __name__ == "__main__":
    main()
