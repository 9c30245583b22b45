"""The frozen execution plan of the unified SNN engine.

An :class:`SNNEnginePlan` owns every decision that used to be threaded
through call sites as kwargs (``threshold``/``leak``/``ltp_prob``/
``backend``/``t_chunk``/``mesh`` across ``ops.py``, ``network.py``,
``trainer.py`` and ``snn_mesh.py``): LIF/STDP parameters, the kernel
backend, the cycle path, VMEM chunking, serving batch size and the
optional neuron-mesh placement.  Plans are frozen dataclasses of plain
Python scalars (plus an optional :class:`jax.sharding.Mesh`), so the
parameters stay concrete at trace time and lower as window-kernel
literals — the engine never hits the traced-parameter fallback the
legacy ``network.run_sample`` path needed.
"""

from __future__ import annotations

import dataclasses

import jax
from jax.sharding import Mesh

from repro.core.lif import LIFParams, lif_params
from repro.core.stdp import STDPParams, stdp_params

_CYCLE_BACKENDS = ("window", "step")
_KERNEL_BACKENDS = ("ref", "interp", "tpu")
_ENCODE_BACKENDS = ("host", "kernel")


def default_kernel_backend() -> str:
    """The platform's kernel path: the compiled Pallas kernels on a TPU,
    the pure-jnp reference everywhere else.  ``"interp"`` (Pallas
    interpret mode) is never a default — only callers that ask get it."""
    return "tpu" if jax.default_backend() == "tpu" else "ref"


@dataclasses.dataclass(frozen=True)
class SNNEnginePlan:
    """Everything the engine needs to place and dispatch SNN work.

    ``w_exp=None`` marks an inference-only plan (SU idle): ``train``
    presents windows without learning, exactly the legacy
    ``run_sample(stdp=None)`` semantics.  Placement is either an
    explicit ``mesh`` (any 1-D neuron or 2-D data × neuron Mesh) or the
    declarative ``mesh_shape=(data, neurons)``, which builds the 2-D
    host mesh on first use — both shard_map the window ops (window path
    only — the step path is a plain XLA scan).  Batch axes shard over
    "data", weights/v/LFSR regfiles over "neurons"; per-stream
    counter-hash seeds are device-independent, so every ``(data,
    neurons)`` factorization is bit-exact with the 1-D and unsharded
    paths.
    """
    # --- LIF / STDP parameters (lower as kernel literals) ---------------
    threshold: int = 192
    leak: int = 16
    w_exp: int | None = 128     # None => SU idle (inference-only plan)
    gain: int = 4
    n_syn: int = 784
    ltp_prob: int = 16
    # --- lateral inhibition and adaptive thresholds (training) ----------
    # Each spike inhibits every other neuron of the population by w_inh
    # the next cycle, and raises its own neuron's threshold offset θ by
    # theta_plus (kernels/ref.py train_window_wta_ref); 0 and 0 leave
    # the population uncoupled, with θ out of the step.
    w_inh: int = 0
    theta_plus: int = 0
    # --- dispatch -------------------------------------------------------
    cycle_backend: str = "window"    # "window" | "step"
    kernel_backend: str | None = None  # "ref" | "interp" | "tpu";
                                       # None = default_kernel_backend()
    t_chunk: int | None = None       # VMEM spike-slab cycles (None = T)
    # --- encoding --------------------------------------------------------
    # Where intensity-driven verbs run the Poisson encode: "host" builds
    # the packed window with encoder.encode_from_counter and feeds the
    # pre-packed kernels; "kernel" fuses the same (bit-exact) counter
    # draw into the window kernels, so spike windows never exist in HBM.
    encode: str = "host"             # "host" | "kernel"
    encode_seed: int = 0             # base counter seed for the draw
    # --- serving / placement -------------------------------------------
    max_batch: int = 8               # serving admission cap per launch
    mesh: Mesh | None = None         # explicit mesh (None = local)
    mesh_shape: tuple | None = None  # declarative (data, neurons) grid;
                                     # built via snn_mesh2d on first use

    def __post_init__(self):
        if self.kernel_backend is None:
            object.__setattr__(self, "kernel_backend",
                               default_kernel_backend())
        if self.cycle_backend not in _CYCLE_BACKENDS:
            raise ValueError(f"cycle_backend must be one of "
                             f"{_CYCLE_BACKENDS}, got "
                             f"{self.cycle_backend!r}")
        if self.kernel_backend not in _KERNEL_BACKENDS:
            raise ValueError(f"kernel_backend must be one of "
                             f"{_KERNEL_BACKENDS}, got "
                             f"{self.kernel_backend!r}")
        if self.encode not in _ENCODE_BACKENDS:
            raise ValueError(f"encode must be one of {_ENCODE_BACKENDS}, "
                             f"got {self.encode!r}")
        if self.encode == "kernel" and self.cycle_backend != "window":
            raise ValueError("in-kernel encode requires the window "
                             "path; use cycle_backend='window'")
        if self.w_inh < 0 or self.theta_plus < 0:
            raise ValueError(f"w_inh and theta_plus must be >= 0, got "
                             f"{self.w_inh} and {self.theta_plus}")
        if self.wta and (self.encode != "kernel" or not self.learn):
            raise ValueError("lateral inhibition and adaptive thresholds "
                             "run in the in-kernel encode train kernel: "
                             "use encode='kernel' and set w_exp")
        if self.t_chunk is not None and self.t_chunk < 1:
            raise ValueError(f"t_chunk must be >= 1, got {self.t_chunk}")
        if self.max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got "
                             f"{self.max_batch}")
        if self.mesh_shape is not None:
            shape = tuple(self.mesh_shape)
            if (len(shape) != 2
                    or not all(isinstance(x, int) and x >= 1
                               for x in shape)):
                raise ValueError(f"mesh_shape must be a (data, neurons) "
                                 f"pair of ints >= 1, got "
                                 f"{self.mesh_shape!r}")
            object.__setattr__(self, "mesh_shape", shape)
            if self.mesh is not None:
                raise ValueError("pass either an explicit mesh or a "
                                 "mesh_shape, not both")
        if ((self.mesh is not None or self.mesh_shape is not None)
                and self.cycle_backend != "window"):
            raise ValueError("mesh placement applies to the window "
                             "path; use cycle_backend='window'")

    # --- derived views ---------------------------------------------------

    def placement(self) -> Mesh | None:
        """The resolved mesh the verbs dispatch over: the explicit
        ``mesh`` when given, else the ``mesh_shape`` grid built over the
        host's devices (Mesh equality is structural, so rebuilding per
        call never re-traces), else None (local execution)."""
        if self.mesh is not None:
            return self.mesh
        if self.mesh_shape is None:
            return None
        from repro.distributed.snn_mesh import snn_mesh2d
        return snn_mesh2d(*self.mesh_shape)

    @property
    def wta(self) -> bool:
        """Whether training steps the population with lateral inhibition
        or adaptive thresholds (θ in the step)."""
        return bool(self.w_inh or self.theta_plus)

    @property
    def learn(self) -> bool:
        """Whether the train verb runs the SU (STDP) at all."""
        return self.w_exp is not None

    def lif(self) -> LIFParams:
        return lif_params(self.threshold, self.leak)

    def stdp(self) -> STDPParams | None:
        if not self.learn:
            return None
        return stdp_params(self.n_syn, self.w_exp, self.gain,
                           self.ltp_prob)

    def window_kwargs(self) -> dict:
        """Static literals for the window kernels (ops.fused_snn_window
        signature); inference-only plans hand the SU zeroed literals +
        train=False, matching the legacy ``_window_params`` encoding."""
        if not self.learn:
            return dict(threshold=self.threshold, leak=self.leak,
                        w_exp=0, gain=0, n_syn=1, ltp_prob=0,
                        train=False)
        return dict(threshold=self.threshold, leak=self.leak,
                    w_exp=self.w_exp, gain=self.gain, n_syn=self.n_syn,
                    ltp_prob=self.ltp_prob, train=True)


def plan_from_config(cfg, block_idx: int = 0,
                     mesh: Mesh | None = None) -> SNNEnginePlan:
    """Build a plan from an ``SNNTrainConfig``-shaped object.

    ``block_idx`` selects the active-learning LTP schedule exactly as
    ``SNNTrainConfig.stdp`` does (block 0 trains at ``ltp_prob``, later
    error-driven blocks at ``ltp_prob_active``).  An explicit ``mesh``
    overrides the config's declarative ``mesh_shape``.
    """
    lp = cfg.ltp_prob if block_idx == 0 else cfg.ltp_prob_active
    shape = getattr(cfg, "mesh_shape", None)
    return SNNEnginePlan(
        threshold=cfg.threshold, leak=cfg.leak, w_exp=cfg.w_exp,
        gain=cfg.gain, n_syn=cfg.n_inputs, ltp_prob=lp,
        cycle_backend=cfg.cycle_backend,
        kernel_backend=cfg.kernel_backend,
        t_chunk=cfg.window_chunk,
        encode=getattr(cfg, "encode", "host"),
        encode_seed=getattr(cfg, "encode_seed", 0),
        w_inh=getattr(cfg, "w_inh", 0),
        theta_plus=getattr(cfg, "theta_plus", 0), mesh=mesh,
        mesh_shape=None if mesh is not None else shape)
