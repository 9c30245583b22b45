"""RV-SNN V1.0 — the paper's SNN instruction set, reified as JAX ops.

Wenquxing 22A extends NutShell's execution stage with an SNN unit (SNNU)
containing the Spike Process Unit (SPU), Neuron Unit (NU) and Synapse
Unit (SU = LTP + LTD), plus an *SNN special register file* next to the
GPRs.  The paper stresses **high computational granularity**: one
instruction performs a whole neuron-row's worth of work so the in-order
pipeline is not stalled by long µop sequences.

This module is the "toolchain" layer: each instruction is a pure JAX
function over an :class:`SnnRegFile`, with the same operand granularity
the hardware has.  The Pallas kernels in ``repro.kernels`` are the TPU
microarchitecture of the same instructions (see DESIGN.md §2); everything
here is the architectural (ISA-level) reference.

Instruction summary (names follow the unit that executes them; the
public paper does not print the exact mnemonics, so these are
reconstructed from §2.2 and flagged as such in DESIGN.md §7):

=============  ====  =====================================================
mnemonic       unit  semantics
=============  ====  =====================================================
``snn.ls``     SPU   load a packed spike vector into the spike register
``snn.sp``     SPU   AND spike reg with a synapse row block, popcount ->
                     valid-spike counts
``snn.nu``     NU    streamlined-LIF update of membrane registers
``snn.su``     SU    single-pass LTP+LTD synapse row update (uses the
                     LFSR register)
``snn.step``   SNNU  fused sp+nu+su for a whole population — the
                     coarse-granularity instruction the paper's speedup
                     comes from
=============  ====  =====================================================
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

from repro.core import lfsr as _lfsr
from repro.core.bitpack import popcount
from repro.core.lif import LIFParams, lif_step
from repro.core.stdp import STDPParams, stdp_update


class SnnRegFile(NamedTuple):
    """The SNN special register file (paper Fig. 2).

    spike:   uint32[w]      packed input spike vector (spike register)
    v:       int32[n]       membrane potentials (neuron registers)
    lfsr:    uint32[n, w]   PRNG lanes (LFSR register, vectorized)
    weights: uint32[n, w]   packed 1-bit synapse rows (synapse memory —
                            architecturally a register-addressed SRAM)
    theta:   int32[n]       adaptive threshold offsets (None: none held).
                            A plan with lateral inhibition or adaptive
                            thresholds fires a neuron at threshold + θ and
                            carries θ across samples, as weights and LFSR
                            lanes; other plans leave it as it is.
    """
    spike: jnp.ndarray
    v: jnp.ndarray
    lfsr: jnp.ndarray
    weights: jnp.ndarray
    theta: jnp.ndarray | None = None


def snn_regfile(weights: jnp.ndarray, seed: int = 0x22A) -> SnnRegFile:
    n, w = weights.shape
    return SnnRegFile(
        spike=jnp.zeros((w,), jnp.uint32),
        v=jnp.zeros((n,), jnp.int32),
        lfsr=_lfsr.seed(seed, n * w).reshape(n, w),
        weights=weights,
        theta=jnp.zeros((n,), jnp.int32),
    )


def snn_regfile_batch(weights: jnp.ndarray, seeds) -> SnnRegFile:
    """B independent register files as one batched SnnRegFile.

    weights: uint32[B, n, w]; seeds: B per-stream LFSR base seeds.
    Every leaf gains a leading stream axis; stream b is exactly
    ``snn_regfile(weights[b], seeds[b])``, so batched execution can be
    checked bit-exactly against B sequential regfiles.
    """
    b, n, w = weights.shape
    if len(seeds) != b:
        raise ValueError(f"need {b} seeds, got {len(seeds)}")
    return SnnRegFile(
        spike=jnp.zeros((b, w), jnp.uint32),
        v=jnp.zeros((b, n), jnp.int32),
        lfsr=jnp.stack([_lfsr.seed(int(s), n * w).reshape(n, w)
                        for s in seeds]),
        weights=weights,
        theta=jnp.zeros((b, n), jnp.int32),
    )


# --- SPU ------------------------------------------------------------------

def snn_ls(rf: SnnRegFile, spike_words: jnp.ndarray) -> SnnRegFile:
    """``snn.ls`` — latch a packed spike vector into the spike register."""
    return rf._replace(spike=spike_words.astype(jnp.uint32))


def snn_sp(rf: SnnRegFile) -> jnp.ndarray:
    """``snn.sp`` — valid-spike counts: popcount(spike & weights) per row."""
    return popcount(jnp.bitwise_and(rf.spike[None, :], rf.weights))


# --- NU -------------------------------------------------------------------

def snn_nu(rf: SnnRegFile, counts: jnp.ndarray, p: LIFParams
           ) -> tuple[SnnRegFile, jnp.ndarray]:
    """``snn.nu`` — streamlined-LIF membrane update; returns fired mask."""
    v_next, fired = lif_step(rf.v, counts, p)
    return rf._replace(v=v_next), fired


# --- SU -------------------------------------------------------------------

def snn_su(rf: SnnRegFile, fired: jnp.ndarray, p: STDPParams) -> SnnRegFile:
    """``snn.su`` — binary stochastic STDP row update on post-spikes."""
    w_out, lf_out = stdp_update(rf.weights, rf.spike, fired, rf.lfsr, p)
    return rf._replace(weights=w_out, lfsr=lf_out)


# --- fused SNNU step --------------------------------------------------------

def snn_step(
    rf: SnnRegFile,
    spike_words: jnp.ndarray,
    lif: LIFParams,
    stdp: STDPParams | None,
    teach: jnp.ndarray | None = None,
) -> tuple[SnnRegFile, jnp.ndarray]:
    """``snn.step`` — one fused SNNU cycle for the whole population.

    spike_words: uint32[w] this cycle's packed input spikes.
    teach:       optional int32[n] supervised teacher current added on the
                 NU adder (positive drives the labeled neuron, negative
                 inhibits the rest).
    stdp:        None => inference only (SU idle).
    Returns (rf', fired bool[n]).
    """
    rf = snn_ls(rf, spike_words)
    counts = snn_sp(rf)
    if teach is not None:
        counts = counts + teach
    rf, fired = snn_nu(rf, counts, lif)
    if stdp is not None:
        rf = snn_su(rf, fired, stdp)
    return rf, fired
