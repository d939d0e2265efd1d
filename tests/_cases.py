"""Configurations, job specs and small readers that more than one test
file uses (the fault plan's fixture, ``clean_faults``, is in conftest)."""

import os

import jax
import numpy as np

from cup3d_tpu.config import SimulationConfig
from cup3d_tpu.obs import metrics as M
from cup3d_tpu.obs import profile as P
from cup3d_tpu.sim.simulation import Simulation


def _tgv(tmp, **kw):
    base = dict(
        bpdx=2, bpdy=2, bpdz=2, levelMax=1, levelStart=0,
        extent=2 * np.pi, CFL=0.3, nu=0.02, rampup=0,
        initCond="taylorGreen", verbose=False, freqDiagnostics=0,
        path4serialization=str(tmp),
    )
    base.update(kw)
    return SimulationConfig(**base)


def tgv_cfg(tmp, **kw):
    """Pipelined 16^3 Taylor-Green vortex, 16 steps (megaloop-eligible)."""
    return _tgv(tmp, **{**dict(nsteps=16, tend=0.0, pipelined=True), **kw})


def iterative_tgv_cfg(tmp, **kw):
    """16^3 Taylor-Green vortex through the iterative solve at the
    production tolerance; the caller says how long (``nsteps``/``tend``)."""
    return _tgv(tmp, **{**dict(poissonSolver="iterative", poissonTol=1e-6,
                               poissonTolRel=1e-4), **kw})


def fish_cfg(tmp, **kw):
    """Pipelined 32^3 StefanFish, 8 steps."""
    base = dict(
        bpdx=1, bpdy=1, bpdz=1, levelMax=1, levelStart=0, block_size=32,
        extent=1.0, CFL=0.3, nu=1e-4, nsteps=8, tend=0.0, rampup=0,
        factory_content="stefanfish L=0.3 T=1.0 xpos=0.5",
        dtype="float32", pipelined=True, verbose=False,
        freqDiagnostics=0, path4serialization=str(tmp),
    )
    base.update(kw)
    return SimulationConfig(**base)


def simulate(cfg):
    sim = Simulation(cfg)
    sim.init()
    sim.simulate()
    return sim


def flight_files(tmp):
    return [f for f in os.listdir(tmp) if f.startswith("flight_")]


def mean_ke(vel):
    v = np.asarray(vel, np.float64)
    return float(np.mean(np.sum(v * v, axis=-1)))


def tgv_spec(**kw):
    """A fleet job: 16^3 Taylor-Green vortex, 8 steps."""
    spec = dict(kind="tgv", n=16, nsteps=8, cfl=0.3)
    spec.update(kw)
    return spec


def delta(before, key):
    """Growth of one metric since the ``M.snapshot()`` in ``before``."""
    return M.snapshot().get(key, 0) - before.get(key, 0)


def scope_paths(thunk):
    """The scope paths (``obs/profile.scope_path``) of every equation
    ``thunk`` traces to, those of nested programs and loop bodies under
    the path of the equation that holds them, as the ``op_name`` of the
    lowered operation has them; nothing is compiled or run."""
    out = set()

    def walk(jaxpr, prefix):
        for eqn in jaxpr.eqns:
            stack = f"{prefix}/{eqn.source_info.name_stack}"
            out.add("/".join(P.scope_path(stack)))
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub, stack)

    walk(jax.make_jaxpr(thunk)().jaxpr, "")
    return out - {""}
