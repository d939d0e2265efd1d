"""Checkpoint / restore of a full run.

The reference parses ``-fsave/saveFreq`` (main.cpp:15381-15385) but ships
no restart serialization (SURVEY.md section 5 names this a capability gap
to fill).  Here a checkpoint is one self-contained pickle holding

- the config (rebuilds solvers/operators deterministically),
- the octree leaf keys (AMR) — topology is data, not pointers,
- every field as numpy (bit-exact),
- time/step/dt/uinf/lambda,
- obstacle kinematic state (Obstacle.__getstate__ drops device arrays;
  chi/udef are re-rasterized from the restored kinematics).

``load_checkpoint`` reconstructs the driver and returns it ready to
``simulate()``; a restored run reproduces the original trajectory to
floating-point determinism of the jitted kernels.
"""

from __future__ import annotations

import dataclasses
import os
import pickle
from typing import Optional

import jax.numpy as jnp
import numpy as np

FORMAT_VERSION = 1


def _driver_kind(driver) -> str:
    from cup3d_tpu.sim.amr import AMRSimulation

    return "amr" if isinstance(driver, AMRSimulation) else "uniform"


def checkpoint_path(directory: str, step: int) -> str:
    return os.path.join(directory, f"ckpt_{step:07d}.pkl")


def build_payload(driver) -> dict:
    """Snapshot everything a checkpoint needs WITHOUT blocking on device
    arrays: ``fields`` holds DEVICE references (immutable in jax, so
    they stay valid snapshots while stepping continues); all host-side
    state (scalars, octree keys, obstacles) is captured synchronously.
    ``materialize_payload`` turns this into the on-disk format."""
    kind = _driver_kind(driver)
    if kind == "amr":
        state = {k: driver._unpad(v) for k, v in driver.state.items()}
        time, step, dt = driver.time, driver.step_idx, driver.dt
        uinf, lam = driver.uinf, driver.lambda_penal
        obstacles = driver.obstacles
        leaves = np.asarray(driver.grid.keys, np.int64)
        next_dump = driver._cadence.next_dump
    else:
        s = driver.sim
        state = s.state
        time, step, dt = s.time, s.step, s.dt
        uinf, lam = s.uinf, s.lambda_penal
        obstacles = s.obstacles
        leaves = None
        next_dump = s.cadence.next_dump
    return {
        "version": FORMAT_VERSION,
        "kind": kind,
        "cfg": dataclasses.asdict(driver.cfg),
        "leaves": leaves,
        "fields": dict(state),
        "time": float(time),
        "step": int(step),
        "dt": float(dt),
        "uinf": np.asarray(uinf, np.float64),
        "lambda_penal": float(lam),
        "next_dump": float(next_dump),
        "obstacles": obstacles,
    }


def materialize_payload(payload: dict) -> dict:
    """Resolve the device field references of ``build_payload`` to numpy
    (blocking only until their async copies land)."""
    out = dict(payload)
    out["fields"] = {k: np.asarray(v) for k, v in payload["fields"].items()}
    return out


def write_payload(payload: dict, path: str) -> str:
    """Atomic, retried checkpoint write (round 10): the payload pickles
    into ``<path>.tmp`` and is promoted with ``os.replace``, so a kill
    (or an armed ``ckpt.write_fail`` injection) at any instant leaves
    either the previous complete file or none — never a truncated
    pickle.  Transient failures retry with backoff + jitter
    (resilience/writeguard.py)."""
    from cup3d_tpu.resilience import faults, writeguard

    def _write(tmp: str) -> None:
        # injection seam: fires on EVERY retry while armed, so a
        # persistent-failure scenario is one multi-count arm
        faults.maybe_raise("ckpt.write_fail", payload.get("step"))
        with open(tmp, "wb") as f:
            pickle.dump(payload, f, protocol=pickle.HIGHEST_PROTOCOL)

    return writeguard.atomic_write(path, _write, site="ckpt")


def save_checkpoint(driver, path: Optional[str] = None) -> str:
    """Synchronous checkpoint (tools/tests; the drivers stream saves off
    the step loop via stream/checkpoint.AsyncCheckpointer instead)."""
    payload = build_payload(driver)
    if path is None:
        path = checkpoint_path(
            driver.cfg.path4serialization, payload["step"]
        )
    return write_payload(materialize_payload(payload), path)


def read_payload(path: str) -> dict:
    """Unpickle + validate one checkpoint payload.  A partial/corrupt
    file (killed writer predating the round-10 atomic writes, disk
    damage, or just not-a-checkpoint) raises ``ValueError`` with a clear
    message instead of an unpickling traceback."""
    try:
        with open(path, "rb") as f:
            payload = pickle.load(f)
    except OSError:
        raise  # missing/unreadable file: the caller's error is clearer
    except Exception as e:
        raise ValueError(
            f"corrupt or truncated checkpoint {path!r}: "
            f"{type(e).__name__}: {e}"
        ) from e
    if not isinstance(payload, dict) or "version" not in payload:
        raise ValueError(
            f"not a cup3d_tpu checkpoint payload: {path!r}"
        )
    if payload["version"] != FORMAT_VERSION:
        raise ValueError(f"unknown checkpoint version {payload['version']}")
    missing = [k for k in ("kind", "cfg", "fields", "time", "step", "dt")
               if k not in payload]
    if missing:
        raise ValueError(
            f"incomplete checkpoint {path!r}: missing keys {missing}"
        )
    return payload


def list_checkpoints(directory: str):
    """``ckpt_*.pkl`` files under ``directory``, oldest step first."""
    try:
        names = os.listdir(directory)
    except OSError:
        return []
    out = []
    for n in names:
        if n.startswith("ckpt_") and n.endswith(".pkl"):
            try:
                step = int(n[len("ckpt_"):-len(".pkl")])
            # jax-lint: allow(JX009, a non-checkpoint filename that
            # merely matches the prefix is skipped by design)
            except ValueError:
                continue
            out.append((step, os.path.join(directory, n)))
    return [p for _, p in sorted(out)]


def latest_valid_checkpoint(directory: str) -> Optional[str]:
    """Newest checkpoint under ``directory`` whose payload validates —
    the crash-restart entry point: a run killed mid-save restarts from
    the last COMPLETE file, skipping anything partial or corrupt."""
    for path in reversed(list_checkpoints(directory)):
        try:
            read_payload(path)
        # jax-lint: allow(JX009, skipping invalid candidates IS this
        # function's contract: the caller restarts from the newest
        # checkpoint that validates)
        except (ValueError, OSError):
            continue
        return path
    return None


def load_checkpoint(path: str, mesh=None):
    """Rebuild the driver (AMRSimulation or Simulation) from a checkpoint,
    ready to continue stepping.  ``mesh`` (a 1-D jax Mesh) restores an AMR
    checkpoint INTO sharded (mesh) mode: fields are padded + sharded over
    the device mesh exactly as a fresh mesh-mode run lays them out —
    checkpoints themselves are layout-free (unpadded numpy), so saves from
    single-device runs restore sharded and vice versa.  Partial/corrupt
    files raise ``ValueError`` (see :func:`read_payload`)."""
    from cup3d_tpu.config import SimulationConfig

    payload = read_payload(path)
    cfg = SimulationConfig(**payload["cfg"])

    if payload["kind"] == "amr":
        from cup3d_tpu.grid.octree import Octree, TreeConfig
        from cup3d_tpu.sim.amr import AMRSimulation

        periodic = tuple(b == "periodic" for b in cfg.bc)
        tree = Octree(
            TreeConfig((cfg.bpdx, cfg.bpdy, cfg.bpdz), cfg.levelMax, periodic),
            0,
        )
        tree.leaves.clear()
        for l, i, j, k in payload["leaves"]:
            tree.leaves[(int(l), int(i), int(j), int(k))] = None
        tree.assert_balanced()
        driver = AMRSimulation(cfg, tree=tree, mesh=mesh)
        driver.state = {
            k: driver._pad(jnp.asarray(v, driver.dtype))
            for k, v in payload["fields"].items()
        }
        driver.time = payload["time"]
        driver.step_idx = payload["step"]
        driver.dt = payload["dt"]
        driver.uinf = payload["uinf"]
        driver.lambda_penal = payload["lambda_penal"]
        driver._cadence.next_dump = payload["next_dump"]
        driver.obstacles = payload["obstacles"]
        for ob in driver.obstacles:
            ob.sim = driver
        # rebuild chi/udef device fields from restored kinematics
        driver.create_obstacles(0.0)
        return driver

    from cup3d_tpu.sim.simulation import Simulation

    driver = Simulation(cfg)
    s = driver.sim
    s.state = {k: jnp.asarray(v, s.dtype) for k, v in payload["fields"].items()}
    s.time = payload["time"]
    s.step = payload["step"]
    s.dt = payload["dt"]
    s.uinf = payload["uinf"]
    s.lambda_penal = payload["lambda_penal"]
    s.cadence.next_dump = payload["next_dump"]
    s.obstacles = payload["obstacles"]
    for ob in s.obstacles:
        ob.sim = s
    driver._setup_operators()
    if s.obstacles:
        driver.pipeline[0].rebuild()  # CreateObstacles: chi/udef
    return driver
