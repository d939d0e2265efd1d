"""SimulationData: all runtime state of a run (reference main.cpp:6600-6677).

The reference keeps five parallel AMR grids (chi, pres, lhs scalar; vel, tmpV
vector).  Here the uniform-grid path keeps one dict of dense device arrays;
``lhs``/``tmpV`` scratch fields are unnecessary because XLA materializes
temporaries inside fused kernels.  The AMR path swaps these for block-batched
arrays with identical keys (``cup3d_tpu.grid.blocks``).
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional

import jax.numpy as jnp
import numpy as np

from cup3d_tpu.config import SimulationConfig
from cup3d_tpu.grid.uniform import BC, UniformGrid
from cup3d_tpu.io.logging import BufferedLogger, Profiler
from cup3d_tpu.ops.poisson import make_poisson_solver


class SimulationData:
    def __init__(self, cfg: SimulationConfig):
        self.cfg = cfg
        shape = cfg.uniform_shape()
        self.grid = UniformGrid(shape, cfg.extents, tuple(BC(b) for b in cfg.bc))
        self.dtype = jnp.dtype(cfg.dtype)

        n3 = shape + (3,)
        self.state: Dict[str, jnp.ndarray] = {
            "vel": jnp.zeros(n3, self.dtype),
            "chi": jnp.zeros(shape, self.dtype),
            "p": jnp.zeros(shape, self.dtype),
            "udef": jnp.zeros(n3, self.dtype),
        }

        self.poisson_solver: Callable = make_poisson_solver(
            self.grid,
            cfg.poissonSolver,
            self.dtype,
            tol_abs=cfg.poissonTol,
            tol_rel=cfg.poissonTolRel,
            mean_constraint=cfg.bMeanConstraint,
        )
        # round 12: record which Krylov path this run compiled (storage
        # dtype + fused-iteration driver) so a bench/telemetry dump can
        # tell the configurations apart without re-deriving env state
        from cup3d_tpu.obs import metrics as obs_metrics
        from cup3d_tpu.ops import precision as _precision

        obs_metrics.gauge("poisson.krylov_bf16").set(
            float(_precision.krylov_dtype() == jnp.bfloat16))
        obs_metrics.gauge("poisson.fused_iteration").set(
            float(_precision.use_fused()))

        # scalars (host side, mirroring main.cpp:15348-15387 defaults)
        self.time: float = 0.0
        self.step: int = 0
        self.dt: float = 0.0
        self.uinf = np.asarray(cfg.uinf, dtype=np.float64)
        self.nu = cfg.nu
        self.lambda_penal = cfg.lambda_penalization

        self.obstacles: List = []  # filled by the obstacle factory
        self.MeshChanged = True
        # device fast path: (name, device array) QoI produced during the
        # step, concatenated and fetched in ONE host read at the end of
        # advance() (every blocking read stalls the dispatch queue);
        # pipelined mode defers that read one step so the transfer overlaps
        # the next step's device work
        self.pending_parts: List = []
        self._uinf_dev = None
        self._uinf_host_src = None    # identity key of the cached upload
        self._uinf_host_cache = None  # device mirror of self.uinf

        self.logger = BufferedLogger(cfg.path4serialization)
        self.profiler = Profiler()
        from cup3d_tpu.io.dump import OutputCadence

        self.cadence = OutputCadence(cfg.tdump, cfg.fdump, cfg.saveFreq)

        # device-resident cell centers + jitted rigid-body velocity field:
        # obstacle code calls body_velocity_field every step (penalization,
        # forces); rebuilding centers on host and dispatching eagerly costs
        # seconds/step at 128^3 (measured on TPU).  Built lazily so
        # obstacle-free runs never hold the (nx,ny,nz,3) array on device.
        self._xc_cache = None
        self._ubody_cache_fn = None
        # cached device lambda mirrors (lambda_device): the DLM constant
        # uploads once and lambda = DLM/dt is computed ON DEVICE from the
        # step's already-uploaded dt scalar; a static lambda uploads once
        # per value.  The old per-step jnp.asarray(self.lambda_penal)
        # re-staged a fresh host float every step (lint rule JX010).
        self._dlm_dev_cache = None
        self._lambda_dev_cache = None
        self._lambda_dev_val = None

    @property
    def xc(self) -> jnp.ndarray:
        if self._xc_cache is None:
            self._xc_cache = jnp.asarray(self.grid.cell_centers(self.dtype))
        return self._xc_cache

    @property
    def _ubody_fn(self):
        if self._ubody_cache_fn is None:
            import jax

            xc = self.xc
            self._ubody_cache_fn = jax.jit(jax.named_scope("Penalization")(
                lambda udef, cm, ut, om: ut
                + jnp.cross(jnp.broadcast_to(om, xc.shape), xc - cm)
                + udef
            ))
        return self._ubody_cache_fn

    @property
    def vel(self) -> jnp.ndarray:
        return self.state["vel"]

    @property
    def chi(self) -> jnp.ndarray:
        return self.state["chi"]

    def lambda_device(self, dt_dev) -> jnp.ndarray:
        """Device-resident penalization lambda for this step.

        DLM > 0 configurations recompute lambda = DLM/dt every step
        (main.cpp:15302-15303): the division runs ON DEVICE against the
        step's dt scalar (already uploaded by advance()), with the DLM
        constant cached after one sanctioned upload — zero steady-state
        host->device traffic.  Static-lambda configurations upload once
        per value.  The host ``lambda_penal`` mirror keeps feeding logs
        and checkpoints unchanged."""
        from cup3d_tpu.analysis.runtime import sanctioned_transfer

        if self.cfg.DLM > 0:
            if self._dlm_dev_cache is None:
                with sanctioned_transfer("scalar-upload"):
                    self._dlm_dev_cache = jnp.asarray(
                        self.cfg.DLM, self.dtype
                    )
            return self._dlm_dev_cache / dt_dev
        if self._lambda_dev_val != self.lambda_penal:
            with sanctioned_transfer("scalar-upload"):
                self._lambda_dev_cache = jnp.asarray(
                    self.lambda_penal, self.dtype
                )
            self._lambda_dev_val = self.lambda_penal
        return self._lambda_dev_cache

    def uinf_device(self) -> jnp.ndarray:
        # pipelined mode keeps uinf device-resident (CreateObstacles sets
        # it from the device transVel); the host self.uinf then only feeds
        # logs and checkpoints
        if self._uinf_dev is not None:
            return self._uinf_dev
        # cache the upload keyed on identity: frame-velocity updates
        # REASSIGN self.uinf (models/pipeline.py, io/checkpoint.py), so
        # `is` tracks staleness without a per-step compare and a constant
        # uinf costs the steady-state loop zero host->device traffic
        # (caught by jax.transfer_guard in tests/test_analysis.py)
        if self._uinf_host_src is not self.uinf:
            from cup3d_tpu.analysis.runtime import sanctioned_transfer

            with sanctioned_transfer("uinf-upload"):
                self._uinf_host_cache = jnp.asarray(
                    self.uinf, dtype=self.dtype
                )
            self._uinf_host_src = self.uinf
        return self._uinf_host_cache
