"""Pallas TPU kernels for the RV-SNN datapath (SPU / NU / SU / fused SNNU).

Layout conventions
------------------
All packed operands are padded on the word axis to a multiple of 128
(the TPU lane width) by ``ops.py``; tail words are zero, which every op
here preserves (AND/popcount ignore zero words; STDP's LTP or-in of a
zero pre-word is a no-op and LTD can only clear).  The neuron axis is
blocked by ``BN`` (multiple of 8, the sublane width).

Time axis (window kernels): **state is VMEM-resident, time is
streamed**.  ``fused_snn_window`` loads the weight block, LFSR block and
membrane block once, then a ``fori_loop`` over the T presentation cycles
reads one (small) packed spike row per cycle and stores one fired row
into the raster — weights/LFSR cross HBM once per *window*, not once per
*cycle*.  The batch kernels order the grid (neuron-block major, batch
minor) so a shared weight block (inference) stays resident across all B
samples of a serving batch, and B independent training streams share one
launch.

Chunked spike streaming: every window kernel takes a ``t_chunk`` grid
dimension (innermost, so per-(block, stream) state carries across
chunks via revisited output blocks; the MXU serving kernel puts the
neuron block innermost and carries its state in VMEM scratch).  VMEM
then holds ``T_chunk x W`` spike words instead of ``T x W`` —
unbounded T at bounded VMEM.  Chunk
boundaries are bit-exact with the unchunked kernel: membrane/weight/
LFSR state is read back from the (still-resident) output block, and a
``t_total`` literal masks the zero-padded ragged tail so padded cycles
advance no state.

In-kernel encode (the ``*_encode`` kernels): the paper's on-core
Poisson encoder (§3.1, P = x per cycle) fused into the window kernels.
Instead of streaming a pre-packed ``uint32[T, W]`` spike window from
HBM, the kernel takes one uint8 intensity per input (packed 4-per-word
as ``uint32[8, W]``) plus a counter seed and draws each cycle's packed
spike row in VMEM via the stateless ``counter_hash`` (keyed on the
absolute cycle — no carried PRNG state, so chunked and sharded launches
regenerate identical spikes).  Input-stream HBM traffic per sample
drops ``T*W*4 -> 32*W`` bytes (= n_in): ~T/8x — 4x at T=32, 16x at
T=128, 256x at T=2048 — and the serving variant reads the per-sample
window length from SMEM, so one launch serves a ragged batch.

VMEM budget (per grid step, BN=128, padded words W<=2048):
  fused step:    in + out blocks of weights and LFSR
                 ~ 4 * BN * W * 4B = 4 MiB at the 64k-synapse extreme.
  train window:  the same 4 MiB of state blocks, plus the streamed
                 spike chunk T_chunk * W * 4B (256 KiB at T_chunk=32,
                 W=2048) and the bool raster chunk T_chunk * BN (4 KiB)
                 — ~4.3 MiB, *independent of T*; the unchunked launch
                 (T_chunk = T) adds T * W * 4B, which caps T near 3k
                 at W=2048 on a ~16 MiB v5e core.
  infer window:  one weight block (2 MiB) + spike chunk + v/count rows
                 — ~2.3 MiB per grid step at T_chunk=32.
  train encode:  the 4 MiB of state blocks + intensity words 8 * W * 4B
                 (64 KiB at W=2048) + the raster chunk — no spike slab
                 at ALL, so VMEM is independent of both T and T_chunk.
  train coupled: (lateral inhibition / adaptive thresholds) the whole
                 population is one block: at 6,400 neurons 3.3 MB per
                 state block, 26 MB for weights and LFSR in and out,
                 double-buffered, the resident int8 weights (5.7 MB at
                 K=896), and per block of cycles the int32 currents,
                 the int8 spike tile and the raster — 59 MB at the
                 352-cycle block (``_coupled_vmem_bytes``); the scoped
                 VMEM limit is raised to 100 MiB.
  infer encode:  the MXU kernel (BN=256, 32 samples a block): the int8
                 spike tile 32 * T_chunk * K (K = n_in rounded up to 128;
                 2.1 MiB at T_chunk=72, K=896, capped at 4 MiB by
                 default), the unpacked int8 weight tile K * BN (224 KiB)
                 and its 32-bit intermediate, one 512-row int32 matmul
                 result (512 KiB), and v and the counts of every neuron
                 block of the sample block (2 * 32 * n * 4B: 1.6 MiB at
                 n=6,400) — ~5.6 MiB at the 6,400-neuron offline shape.

The fused kernels are the TPU microarchitecture of the paper's
coarse-granularity ``snn.step`` instruction: one pass through VMEM does
spike-process + LIF + STDP, where the unfused path round-trips HBM
between the three stages — and the window kernels extend the same
argument across the time axis and the batch/stream axis
(benchmarks/kernels_bench.py measures all three levels).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# --- in-kernel LFSR (bit-exact with repro.core.lfsr) -------------------------

def _lfsr_step(state):
    fb = state
    for sh in (2, 3, 5):
        fb = jnp.bitwise_xor(fb, jnp.right_shift(state, jnp.uint32(sh)))
    fb = jnp.bitwise_and(fb, jnp.uint32(1))
    return jnp.bitwise_and(
        jnp.bitwise_or(jnp.right_shift(state, jnp.uint32(1)),
                       jnp.left_shift(fb, jnp.uint32(15))),
        jnp.uint32(0xFFFF))


def _popcount_rows(words):
    """uint32[bn, w] -> int32[bn] total set bits per row."""
    return jnp.sum(jax.lax.population_count(words).astype(jnp.int32),
                   axis=-1)


# --- in-kernel Poisson encode (bit-exact with encoder.encode_from_counter) ---

def _counter_hash(seed, cycle, idx):
    """Stateless counter draw; mirror of repro.core.lfsr.counter_hash."""
    h = (seed + cycle * jnp.uint32(0x9E3779B9)
         + idx * jnp.uint32(0x85EBCA6B))
    h = jnp.bitwise_xor(h, jnp.right_shift(h, jnp.uint32(16)))
    h = h * jnp.uint32(0x7FEB352D)
    h = jnp.bitwise_xor(h, jnp.right_shift(h, jnp.uint32(15)))
    h = h * jnp.uint32(0x846CA68B)
    return jnp.bitwise_xor(h, jnp.right_shift(h, jnp.uint32(16)))


def _encode_cycle(seed, cycle, iw):
    """Generate one cycle's packed spike row in VMEM.

    iw: uint32[8, W] intensity words — byte ``b`` of ``iw[k, wi]`` is the
    uint8 intensity of input ``wi*32 + 4k + b`` (ops.py packs this
    layout; 1 byte of HBM traffic per input instead of T/8 bytes of
    pre-packed spikes).  Returns uint32[1, W]: bit ``j`` of word ``wi``
    fires iff ``counter_hash(seed, cycle, wi*32+j) & 0xFF < intensity``
    — bit-exact with the host oracle, and intensity 0 (incl. all
    padding) never fires.
    """
    w = iw.shape[-1]
    base_idx = jax.lax.broadcasted_iota(jnp.uint32, (1, w),
                                        1) * jnp.uint32(32)
    out = jnp.zeros((1, w), jnp.uint32)
    for k in range(8):          # static: 8 intensity words x 4 bytes
        word = iw[k][None, :]
        for b in range(4):
            j = 4 * k + b
            inten = jnp.bitwise_and(
                jnp.right_shift(word, jnp.uint32(8 * b)),
                jnp.uint32(0xFF))
            h = _counter_hash(seed, cycle, base_idx + jnp.uint32(j))
            bit = (jnp.bitwise_and(h, jnp.uint32(0xFF))
                   < inten).astype(jnp.uint32)
            out = jnp.bitwise_or(out, jnp.left_shift(bit, jnp.uint32(j)))
    return out


# --- block specs of the batched window grids ---------------------------------
# Grid (i, j, k) = (neuron block, batch stream, time chunk).  The batch
# dim of every block is squeezed (None), so kernels see 2-D state blocks
# and 1-D per-neuron rows.  Per-neuron [B, n] arrays cross the call as
# [B, 1, n]: a block's last two dims must be multiples of (8, 128) or
# equal the array's, and (1, block_n) on [B, 1, n] is the latter.
# Per-stream scalars (seeds, LTP probabilities, window lengths) are whole
# i32[B] SMEM operands indexed by ``pl.program_id(1)``.

_SMEM_WHOLE = pl.BlockSpec(memory_space=pltpu.SMEM)


def _state_block(bn, w):
    return pl.BlockSpec((None, bn, w), lambda i, j, k: (j, i, 0))


def _slab_block(rows, w):
    return pl.BlockSpec((None, rows, w), lambda i, j, k: (j, k, 0))


def _row_block(bn):
    return pl.BlockSpec((None, None, bn), lambda i, j, k: (j, 0, i))


def _intens_block(w):
    return pl.BlockSpec((None, 8, w), lambda i, j, k: (j, 0, 0))


def _raster_block(tc, bn):
    return pl.BlockSpec((None, tc, bn), lambda i, j, k: (j, k, i))


# --- SPU: spike process -------------------------------------------------------

def _spike_process_kernel(s_ref, w_ref, o_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    s = s_ref[...]          # (1, BW)
    w = w_ref[...]          # (BN, BW)
    o_ref[...] += _popcount_rows(jnp.bitwise_and(s, w))


def spike_process(spikes, weights, *, block_n=128, block_w=512,
                  interpret=False):
    """SPU kernel.  spikes u32[w], weights u32[n, w] -> counts i32[n].

    Requires n % block_n == 0 and w % block_w == 0 (ops.py pads).
    """
    n, w = weights.shape
    grid = (n // block_n, w // block_w)
    return pl.pallas_call(
        _spike_process_kernel,
        out_shape=jax.ShapeDtypeStruct((n,), jnp.int32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_w), lambda i, j: (0, j)),
            pl.BlockSpec((block_n, block_w), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((block_n,), lambda i, j: (i,)),
        interpret=interpret,
    )(spikes[None, :], weights)


# --- NU: streamlined LIF ------------------------------------------------------

def _lif_kernel(threshold: int, leak: int, v_ref, c_ref, v_out_ref, f_ref):
    # threshold/leak are Python ints -> lowered as literals.
    v = v_ref[...] + c_ref[...]
    fired = v >= threshold
    v_out_ref[...] = jnp.where(
        fired, jnp.int32(0), jnp.maximum(v - leak, jnp.int32(0)))
    f_ref[...] = fired


def lif_step(v, count, threshold: int, leak: int, *, block_n=128,
             interpret=False):
    """NU kernel.  v, count i32[n] -> (v' i32[n], fired bool[n])."""
    n = v.shape[0]
    kern = functools.partial(_lif_kernel, int(threshold), int(leak))
    return pl.pallas_call(
        kern,
        out_shape=(jax.ShapeDtypeStruct((n,), jnp.int32),
                   jax.ShapeDtypeStruct((n,), jnp.bool_)),
        grid=(n // block_n,),
        in_specs=[pl.BlockSpec((block_n,), lambda i: (i,)),
                  pl.BlockSpec((block_n,), lambda i: (i,))],
        out_specs=(pl.BlockSpec((block_n,), lambda i: (i,)),
                   pl.BlockSpec((block_n,), lambda i: (i,))),
        interpret=interpret,
    )(v, count)


# --- SU: binary stochastic STDP ----------------------------------------------

def _stdp_body(w, pre, fired, st, *, w_exp, gain, n_syn, ltp_prob):
    """Shared LTP+LTD dataflow (uint32 blocks).  Returns (w', st')."""
    # column mask via int32: Mosaic has no i1 shape cast (n,) -> (n, 1)
    fired_u = fired.astype(jnp.int32)[:, None] != 0
    s1 = _lfsr_step(st)
    x_ltp = jnp.bitwise_and(s1, jnp.uint32(0x3FF))
    s2 = _lfsr_step(s1)
    x_ltd = jnp.bitwise_and(s2, jnp.uint32(0x3FF))
    st_out = jnp.where(fired_u, s2, st)

    potentiate = x_ltp <= jnp.uint32(ltp_prob)
    ltp = jnp.where(potentiate, jnp.bitwise_or(w, pre), w)
    pc = _popcount_rows(ltp)
    excess = (pc - jnp.int32(w_exp)) * jnp.int32(gain) * 1024 \
        // jnp.int32(n_syn)
    prob = jnp.clip(excess, 0, 1023).astype(jnp.uint32)
    depress = x_ltd <= prob[:, None]
    ltd = jnp.where(depress, jnp.bitwise_and(ltp, pre), ltp)
    w_out = jnp.where(fired_u, ltd, w)
    return w_out, st_out


def _stdp_kernel(w_exp, gain, n_syn, ltp_prob,
                 w_ref, pre_ref, f_ref, st_ref, wo_ref, sto_ref):
    w_out, st_out = _stdp_body(
        w_ref[...], pre_ref[...], f_ref[...], st_ref[...],
        w_exp=w_exp, gain=gain, n_syn=n_syn, ltp_prob=ltp_prob)
    wo_ref[...] = w_out
    sto_ref[...] = st_out


def stdp_update(weights, pre_spikes, post_fired, lfsr_state, *,
                w_exp: int, gain: int, n_syn: int, ltp_prob: int,
                block_n=128, interpret=False):
    """SU kernel.  Whole word axis in-block (row popcount is global).

    weights/lfsr u32[n, w], pre u32[w], fired bool[n]
    -> (weights' u32[n, w], lfsr' u32[n, w]).
    """
    n, w = weights.shape
    kern = functools.partial(_stdp_kernel, w_exp, gain, n_syn, ltp_prob)
    return pl.pallas_call(
        kern,
        out_shape=(jax.ShapeDtypeStruct((n, w), jnp.uint32),
                   jax.ShapeDtypeStruct((n, w), jnp.uint32)),
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, w), lambda i: (i, 0)),
            pl.BlockSpec((1, w), lambda i: (0, 0)),
            pl.BlockSpec((block_n,), lambda i: (i,)),
            pl.BlockSpec((block_n, w), lambda i: (i, 0)),
        ],
        out_specs=(pl.BlockSpec((block_n, w), lambda i: (i, 0)),
                   pl.BlockSpec((block_n, w), lambda i: (i, 0))),
        interpret=interpret,
    )(weights, pre_spikes[None, :], post_fired, lfsr_state)


# --- fused SNNU step (the paper's coarse-granularity instruction) -------------

def _fused_kernel(threshold, leak, w_exp, gain, n_syn, ltp_prob, train,
                  w_ref, pre_ref, v_ref, st_ref, t_ref,
                  wo_ref, vo_ref, f_ref, sto_ref):
    w = w_ref[...]
    pre = pre_ref[...]
    counts = _popcount_rows(jnp.bitwise_and(pre, w)) + t_ref[...]
    v = v_ref[...] + counts
    fired = v >= threshold
    vo_ref[...] = jnp.where(
        fired, jnp.int32(0), jnp.maximum(v - leak, jnp.int32(0)))
    f_ref[...] = fired
    if train:
        w_out, st_out = _stdp_body(
            w, pre, fired, st_ref[...],
            w_exp=w_exp, gain=gain, n_syn=n_syn, ltp_prob=ltp_prob)
    else:
        w_out, st_out = w, st_ref[...]
    wo_ref[...] = w_out
    sto_ref[...] = st_out


def fused_snn_step(weights, pre_spikes, v, lfsr_state, teach, *,
                   threshold: int, leak: int, w_exp: int, gain: int,
                   n_syn: int, ltp_prob: int, train: bool = True,
                   block_n=128, interpret=False):
    """One fused SNNU cycle: SPU + NU + SU in a single VMEM pass.

    Returns (weights', v', fired, lfsr').
    """
    n, w = weights.shape
    kern = functools.partial(_fused_kernel, int(threshold), int(leak),
                             w_exp, gain, n_syn, ltp_prob, train)
    return pl.pallas_call(
        kern,
        out_shape=(jax.ShapeDtypeStruct((n, w), jnp.uint32),
                   jax.ShapeDtypeStruct((n,), jnp.int32),
                   jax.ShapeDtypeStruct((n,), jnp.bool_),
                   jax.ShapeDtypeStruct((n, w), jnp.uint32)),
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, w), lambda i: (i, 0)),
            pl.BlockSpec((1, w), lambda i: (0, 0)),
            pl.BlockSpec((block_n,), lambda i: (i,)),
            pl.BlockSpec((block_n, w), lambda i: (i, 0)),
            pl.BlockSpec((block_n,), lambda i: (i,)),
        ],
        out_specs=(pl.BlockSpec((block_n, w), lambda i: (i, 0)),
                   pl.BlockSpec((block_n,), lambda i: (i,)),
                   pl.BlockSpec((block_n,), lambda i: (i,)),
                   pl.BlockSpec((block_n, w), lambda i: (i, 0))),
        interpret=interpret,
    )(weights, pre_spikes[None, :], v, lfsr_state, teach)


# --- batched + chunked training window (B streams x T cycles per launch) -----

def _train_window_kernel(threshold, leak, w_exp, gain, n_syn,
                         t_chunk, t_total,
                         lp_ref, w_ref, s_ref, v_ref, st_ref, t_ref,
                         wo_ref, vo_ref, f_ref, sto_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        wo_ref[...] = w_ref[...]
        vo_ref[...] = v_ref[...]
        sto_ref[...] = st_ref[...]

    # per-stream LTP probability: an SMEM scalar operand rather than a
    # kernel literal, so the B streams of one launch can run different
    # active-learning schedules (ltp_prob vs ltp_prob_active)
    ltp_prob = lp_ref[pl.program_id(1)]
    teach = t_ref[...]
    base = k * t_chunk
    masked = t_total % t_chunk != 0   # zero-padded ragged tail present

    def cycle(t, carry):
        w, v, st = carry
        pre = s_ref[pl.ds(t, 1), :]                     # (1, W)
        counts = _popcount_rows(jnp.bitwise_and(pre, w)) + teach
        v_int = v + counts
        fired = v_int >= threshold
        v_next = jnp.where(
            fired, jnp.int32(0), jnp.maximum(v_int - leak, jnp.int32(0)))
        if masked:
            active = base + t < t_total
            fired = jnp.logical_and(fired, active)
            v_next = jnp.where(active, v_next, v)
        f_ref[pl.ds(t, 1), :] = fired[None, :]
        # masked `fired` also gates STDP: _stdp_body only commits w/LFSR
        # for fired rows, so padded cycles advance no state.
        w, st = _stdp_body(w, pre, fired, st, w_exp=w_exp, gain=gain,
                           n_syn=n_syn, ltp_prob=ltp_prob)
        return w, v_next, st

    w, v, st = jax.lax.fori_loop(
        0, t_chunk, cycle, (wo_ref[...], vo_ref[...], sto_ref[...]))
    wo_ref[...] = w
    vo_ref[...] = v
    sto_ref[...] = st


def train_window_batch(weights, spike_trains, v, lfsr_state, teach, *,
                       threshold: int, leak: int, w_exp: int, gain: int,
                       n_syn: int, ltp_prob, block_n=128,
                       t_chunk: int | None = None,
                       t_total: int | None = None, interpret=False):
    """B independent training streams, T fused SNNU cycles each.

    weights/lfsr u32[B, n, w], spike_trains u32[B, T, w], v i32[B, n],
    teach i32[B, n].  Grid is (neuron blocks, batch, time chunks) —
    neuron-block major, batch next, chunk minor, so each stream's state
    block stays VMEM-resident across all its chunks (the chunk axis
    revisits the same output block; state is carried by reading it
    back).  Per stream this is bit-exact with :func:`fused_snn_window`
    (including the LFSR sequence).

    ``ltp_prob`` is an int shared by every stream or an i32[B] vector —
    it enters the kernel as a whole-array SMEM operand indexed by the
    batch program id, NOT a lowering literal, so parallel-mode training
    keeps per-block active-learning schedules in a single launch.

    ``t_chunk`` bounds the spike words in VMEM to t_chunk * w per grid
    step (default: the whole window).  ``t_total`` masks the cycles
    beyond the true window length when T was zero-padded up to a chunk
    multiple; padded cycles store fired=False and advance no state.

    Returns (weights', v', fired bool[B, T, n], lfsr').
    """
    b, n, w = weights.shape
    t_steps = spike_trains.shape[1]
    tc = t_steps if t_chunk is None else min(t_chunk, t_steps)
    if t_steps % tc != 0:
        raise ValueError(f"T={t_steps} not a multiple of t_chunk={tc}; "
                         "pad the window (ops.py does)")
    tt = t_steps if t_total is None else t_total
    lp = jnp.asarray(ltp_prob, jnp.int32)
    if lp.ndim == 0:
        lp = jnp.broadcast_to(lp, (b,))
    if lp.shape != (b,):
        raise ValueError(f"ltp_prob must be a scalar or shape ({b},), "
                         f"got {lp.shape}")
    kern = functools.partial(_train_window_kernel, int(threshold),
                             int(leak), w_exp, gain, n_syn, tc, tt)
    w2, v2, fired, s2 = pl.pallas_call(
        kern,
        out_shape=(jax.ShapeDtypeStruct((b, n, w), jnp.uint32),
                   jax.ShapeDtypeStruct((b, 1, n), jnp.int32),
                   jax.ShapeDtypeStruct((b, t_steps, n), jnp.bool_),
                   jax.ShapeDtypeStruct((b, n, w), jnp.uint32)),
        grid=(n // block_n, b, t_steps // tc),
        in_specs=[
            _SMEM_WHOLE,
            _state_block(block_n, w),
            _slab_block(tc, w),
            _row_block(block_n),
            _state_block(block_n, w),
            _row_block(block_n),
        ],
        out_specs=(
            _state_block(block_n, w),
            _row_block(block_n),
            _raster_block(tc, block_n),
            _state_block(block_n, w),
        ),
        interpret=interpret,
    )(lp, weights, spike_trains, v[:, None], lfsr_state, teach[:, None])
    return w2, v2[:, 0], fired, s2


# --- time-resident fused window (T cycles per launch) -------------------------

def _window_infer_kernel(threshold, leak, t_chunk, t_total,
                         w_ref, s_ref, v_ref, t_ref, vo_ref, f_ref):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        vo_ref[...] = v_ref[...]

    w = w_ref[...]
    teach = t_ref[...]
    base = k * t_chunk
    masked = t_total % t_chunk != 0

    def cycle(t, v):
        pre = s_ref[pl.ds(t, 1), :]                     # (1, W)
        v_int = v + _popcount_rows(jnp.bitwise_and(pre, w)) + teach
        fired = v_int >= threshold
        v_next = jnp.where(
            fired, jnp.int32(0), jnp.maximum(v_int - leak, jnp.int32(0)))
        if masked:
            active = base + t < t_total
            fired = jnp.logical_and(fired, active)
            v_next = jnp.where(active, v_next, v)
        f_ref[pl.ds(t, 1), :] = fired[None, :]
        return v_next

    vo_ref[...] = jax.lax.fori_loop(0, t_chunk, cycle, vo_ref[...])


def fused_snn_window(weights, spike_train, v, lfsr_state, teach, *,
                     threshold: int, leak: int, w_exp: int, gain: int,
                     n_syn: int, ltp_prob: int, train: bool = True,
                     block_n=128, t_chunk: int | None = None,
                     t_total: int | None = None, interpret=False):
    """T fused SNNU cycles with VMEM-resident state (one stream).

    spike_train: uint32[T, w] — the presentation window, streamed one
    row per inner-loop cycle while weights/v/LFSR stay resident; with
    ``t_chunk`` set, VMEM holds one t_chunk-row slab of the window at a
    time (see :func:`train_window_batch` for the carry/masking scheme).
    Per cycle this is bit-exact with :func:`fused_snn_step` (the LFSR
    advances through the identical sequence).

    ``train=True`` is the B=1 case of :func:`train_window_batch`.
    ``train=False`` (SU idle) dispatches to a read-only variant whose
    launch declares no weight/LFSR outputs — those arrays cross HBM
    once inbound and the originals are passed through — so the
    inference window pays none of the state write-back traffic.

    Returns (weights', v', fired bool[T, n], lfsr').
    """
    n, w = weights.shape
    t_steps = spike_train.shape[0]
    tc = t_steps if t_chunk is None else min(t_chunk, t_steps)
    if t_steps % tc != 0:
        raise ValueError(f"T={t_steps} not a multiple of t_chunk={tc}; "
                         "pad the window (ops.py does)")
    tt = t_steps if t_total is None else t_total
    if not train:
        v2, fired = pl.pallas_call(
            functools.partial(_window_infer_kernel, int(threshold),
                              int(leak), tc, tt),
            out_shape=(jax.ShapeDtypeStruct((n,), jnp.int32),
                       jax.ShapeDtypeStruct((t_steps, n), jnp.bool_)),
            grid=(n // block_n, t_steps // tc),
            in_specs=[
                pl.BlockSpec((block_n, w), lambda i, k: (i, 0)),
                pl.BlockSpec((tc, w), lambda i, k: (k, 0)),
                pl.BlockSpec((block_n,), lambda i, k: (i,)),
                pl.BlockSpec((block_n,), lambda i, k: (i,)),
            ],
            out_specs=(pl.BlockSpec((block_n,), lambda i, k: (i,)),
                       pl.BlockSpec((tc, block_n), lambda i, k: (k, i))),
            interpret=interpret,
        )(weights, spike_train, v, teach)
        return weights, v2, fired, lfsr_state
    w2, v2, fired, s2 = train_window_batch(
        weights[None], spike_train[None], v[None], lfsr_state[None],
        teach[None], threshold=threshold, leak=leak, w_exp=w_exp,
        gain=gain, n_syn=n_syn, ltp_prob=ltp_prob, block_n=block_n,
        t_chunk=tc, t_total=tt, interpret=interpret)
    return w2[0], v2[0], fired[0], s2[0]


# --- batched inference window (serving path) ----------------------------------

def _infer_window_kernel(threshold, leak, t_chunk, t_total,
                         w_ref, s_ref, o_ref, vo_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        vo_ref[...] = jnp.zeros_like(vo_ref)

    w = w_ref[...]
    base = k * t_chunk
    masked = t_total % t_chunk != 0

    def cycle(t, carry):
        v, acc = carry
        pre = s_ref[pl.ds(t, 1), :]                     # (1, W)
        v_int = v + _popcount_rows(jnp.bitwise_and(pre, w))
        fired = v_int >= threshold
        v_next = jnp.where(
            fired, jnp.int32(0), jnp.maximum(v_int - leak, jnp.int32(0)))
        if masked:
            active = base + t < t_total
            fired = jnp.logical_and(fired, active)
            v_next = jnp.where(active, v_next, v)
        return v_next, acc + fired.astype(jnp.int32)

    v, acc = jax.lax.fori_loop(
        0, t_chunk, cycle, (vo_ref[...], o_ref[...]))
    o_ref[...] = acc
    vo_ref[...] = v


def infer_window_batch(weights, spike_trains, *, threshold: int,
                       leak: int, block_n=128, t_chunk: int | None = None,
                       t_total: int | None = None, interpret=False):
    """Serving kernel: B frozen-weight windows per launch.

    spike_trains: uint32[B, T, w].  Grid is (neuron blocks, batch, time
    chunks) with batch/chunk minor, so each weight block is fetched once
    and reused for all B samples and all chunks.  Membrane state starts
    from reset (v=0), matching ``reset_between_samples`` semantics, and
    carries across chunks through a revisited v output block (discarded
    by the caller).

    Returns spike counts int32[B, n] over the window.
    """
    n, w = weights.shape
    b, t_steps, _ = spike_trains.shape
    tc = t_steps if t_chunk is None else min(t_chunk, t_steps)
    if t_steps % tc != 0:
        raise ValueError(f"T={t_steps} not a multiple of t_chunk={tc}; "
                         "pad the window (ops.py does)")
    tt = t_steps if t_total is None else t_total
    kern = functools.partial(_infer_window_kernel, int(threshold),
                             int(leak), tc, tt)
    counts, _ = pl.pallas_call(
        kern,
        out_shape=(jax.ShapeDtypeStruct((b, 1, n), jnp.int32),
                   jax.ShapeDtypeStruct((b, 1, n), jnp.int32)),
        grid=(n // block_n, b, t_steps // tc),
        in_specs=[
            pl.BlockSpec((block_n, w), lambda i, j, k: (i, 0)),
            _slab_block(tc, w),
        ],
        out_specs=(_row_block(block_n), _row_block(block_n)),
        interpret=interpret,
    )(weights, spike_trains)
    return counts[:, 0]


# --- encode-fused windows: spikes generated in VMEM, never read from HBM -----

def _t_grid(n_steps: int, t_chunk: int | None) -> tuple[int, int]:
    """(effective chunk, padded cycle count) for an encode-path launch."""
    tc = n_steps if t_chunk is None else max(1, min(t_chunk, n_steps))
    return tc, -(-n_steps // tc) * tc


def _window_infer_enc_kernel(threshold, leak, t_chunk, t_total,
                             seed_ref, w_ref, iw_ref, v_ref, t_ref,
                             vo_ref, f_ref):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        vo_ref[...] = v_ref[...]

    w = w_ref[...]
    iw = iw_ref[...]
    teach = t_ref[...]
    seed = seed_ref[0].astype(jnp.uint32)
    base = k * t_chunk
    masked = t_total % t_chunk != 0

    def cycle(t, v):
        pre = _encode_cycle(seed, (base + t).astype(jnp.uint32), iw)
        v_int = v + _popcount_rows(jnp.bitwise_and(pre, w)) + teach
        fired = v_int >= threshold
        v_next = jnp.where(
            fired, jnp.int32(0), jnp.maximum(v_int - leak, jnp.int32(0)))
        if masked:
            active = base + t < t_total
            fired = jnp.logical_and(fired, active)
            v_next = jnp.where(active, v_next, v)
        f_ref[pl.ds(t, 1), :] = fired[None, :]
        return v_next

    vo_ref[...] = jax.lax.fori_loop(0, t_chunk, cycle, vo_ref[...])


def _train_window_enc_kernel(threshold, leak, w_exp, gain, n_syn,
                             t_chunk, t_total,
                             lp_ref, seed_ref, w_ref, iw_ref, v_ref,
                             st_ref, t_ref, wo_ref, vo_ref, f_ref, sto_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        wo_ref[...] = w_ref[...]
        vo_ref[...] = v_ref[...]
        sto_ref[...] = st_ref[...]

    j = pl.program_id(1)
    ltp_prob = lp_ref[j]
    seed = seed_ref[j].astype(jnp.uint32)
    iw = iw_ref[...]
    teach = t_ref[...]
    base = k * t_chunk
    masked = t_total % t_chunk != 0

    def cycle(t, carry):
        w, v, st = carry
        pre = _encode_cycle(seed, (base + t).astype(jnp.uint32), iw)
        counts = _popcount_rows(jnp.bitwise_and(pre, w)) + teach
        v_int = v + counts
        fired = v_int >= threshold
        v_next = jnp.where(
            fired, jnp.int32(0), jnp.maximum(v_int - leak, jnp.int32(0)))
        if masked:
            active = base + t < t_total
            fired = jnp.logical_and(fired, active)
            v_next = jnp.where(active, v_next, v)
        f_ref[pl.ds(t, 1), :] = fired[None, :]
        # padded cycles: masked `fired` gates STDP (see train kernel)
        w, st = _stdp_body(w, pre, fired, st, w_exp=w_exp, gain=gain,
                           n_syn=n_syn, ltp_prob=ltp_prob)
        return w, v_next, st

    w, v, st = jax.lax.fori_loop(
        0, t_chunk, cycle, (wo_ref[...], vo_ref[...], sto_ref[...]))
    wo_ref[...] = w
    vo_ref[...] = v
    sto_ref[...] = st


def _train_window_enc_coupled(threshold, leak, w_exp, gain, n_syn,
                              t_chunk, t_total, w_inh, theta_plus,
                              lp_ref, seed_ref, w_ref, iw_ref, x_ref, v_ref,
                              st_ref, t_ref, th_ref, wo_ref, vo_ref, f_ref,
                              sto_ref, tho_ref, rc_ref, fp_ref, wu_ref,
                              cur_ref, s_ref):
    """The train body of a population with an adaptive threshold per
    neuron and, where ``w_inh`` is set, lateral inhibition.

    The block holds the whole population, in groups of 128 neurons; its
    per-neuron rows (v, θ, teach, the raster and the last cycle's spikes
    ``fp_ref``) are laid out (groups, 128), eight groups to a vreg.
    Between two weight changes a neuron's input current ``popcount(pre
    & w)`` does not depend on its membrane, so the currents of a block
    of ``t_chunk`` cycles are computed on the MXU with the weights
    frozen, and the LIF is then scanned over the block:

    * at the launch's first block the packed weights are unpacked into
      the resident int8 scratch ``wu_ref`` (group, input, neuron);
    * each block draws its spikes as a dense 0/1 int8 tile ``s_ref``
      (cycle, input) and computes, group by group, ``cur_ref`` (group x
      cycle, neuron) = spikes @ weights, exact in int8 x int8 -> int32;
    * each cycle steps the whole population from the cycle's row of
      every group (one strided load): input current, inhibition ``w_inh
      * (F - f_j)`` from the last cycle's spikes (F their sum), LIF
      against ``threshold + θ``, ``θ += theta_plus`` on a spike;
    * only in a cycle with a spike, for each group with a spike: STDP
      on its packed rows and LFSR lanes (the others commit nothing and
      their lanes do not advance) with the cycle's packed input row,
      then the group's int8 columns are unpacked again and its currents
      for the whole block recomputed from the new weights.  The rows up
      to this cycle were already consumed, so every current used at
      cycle t comes from the weights as they stand after cycle t - 1's
      STDP, as in :func:`repro.kernels.ref.train_window_wta_ref`.

    ``rc_ref`` counts the recomputes: (cycle, group) pairs with a spike.
    """
    k = pl.program_id(2)
    n_groups, lanes = fp_ref.shape
    kk = x_ref.shape[-1]
    j = pl.program_id(1)
    ltp_prob = lp_ref[j]
    seed = seed_ref[j].astype(jnp.uint32)
    base = k * t_chunk

    def rows(g):
        return pl.ds(pl.multiple_of(g * lanes, lanes), lanes)

    def currents(g):
        """The group's currents over the whole block from ``wu_ref``."""
        cur_ref[pl.ds(pl.multiple_of(g * t_chunk, t_chunk), t_chunk), :] = \
            jnp.dot(s_ref[...], wu_ref[g], preferred_element_type=jnp.int32)

    @pl.when(k == 0)
    def _init():
        wo_ref[...] = w_ref[...]
        vo_ref[...] = v_ref[...]
        sto_ref[...] = st_ref[...]
        tho_ref[...] = th_ref[...]
        fp_ref[...] = jnp.zeros_like(fp_ref)
        rc_ref[...] = jnp.zeros_like(rc_ref)

        def unpack(g, c):
            wu_ref[g] = _unpack_weights(w_ref[rows(g), :], kk)
            return c

        jax.lax.fori_loop(0, n_groups, unpack, 0)

    # the block's spikes, one input per lane, `draw` cycles at a time
    # (whole int8 tiles of 32 rows where the block allows)
    inten = x_ref[...].astype(jnp.uint32)
    draw = 32 if t_chunk % 32 == 0 else t_chunk

    def spikes_at(r, c):
        row = pl.multiple_of(r * draw, draw)
        cyc = (base + row + jax.lax.broadcasted_iota(jnp.int32, (draw, 1), 0)
               ).astype(jnp.uint32)
        s_ref[pl.ds(row, draw), :] = _encode_tile(seed, cyc, inten)
        return c

    def block_currents(g, c):
        currents(g)
        return c

    jax.lax.fori_loop(0, t_chunk // draw, spikes_at, 0)
    jax.lax.fori_loop(0, n_groups, block_currents, 0)

    iw = iw_ref[...]
    teach = t_ref[...]
    masked = t_total % t_chunk != 0

    def learn(t):
        pre = _encode_cycle(seed, (base + t).astype(jnp.uint32), iw)

        def group(g, c):
            fired = fp_ref[pl.ds(g, 1), :]

            @pl.when(jnp.sum(fired) > 0)
            def _stdp():
                r = rows(g)
                w2, st2 = _stdp_body(wo_ref[r, :], pre, fired[0],
                                     sto_ref[r, :], w_exp=w_exp, gain=gain,
                                     n_syn=n_syn, ltp_prob=ltp_prob)
                wo_ref[r, :] = w2
                sto_ref[r, :] = st2
                wu_ref[g] = _unpack_weights(w2, kk)
                currents(g)
                rc_ref[...] += 1

            return c

        jax.lax.fori_loop(0, n_groups, group, 0)

    def cycle(t, carry):
        v, theta, f, total = carry
        u = v + cur_ref[pl.ds(t, n_groups, stride=t_chunk), :] + teach
        if w_inh:
            u = u - jnp.int32(w_inh) * (total - f)
        fired = u >= threshold + theta
        v_next = jnp.where(
            fired, jnp.int32(0), jnp.maximum(u - leak, jnp.int32(0)))
        if masked:
            active = base + t < t_total
            fired = jnp.logical_and(fired, active)
            v_next = jnp.where(active, v_next, v)
        spikes = fired.astype(jnp.int32)
        f_ref[t] = fired
        if theta_plus:
            theta = theta + jnp.int32(theta_plus) * spikes
        total = jnp.sum(spikes)

        @pl.when(total > 0)
        def _learn():
            fp_ref[...] = spikes
            learn(t)

        return v_next, theta, spikes, total

    f0 = fp_ref[...]
    v, theta, f, _ = jax.lax.fori_loop(
        0, t_chunk, cycle, (vo_ref[...], tho_ref[...], f0, jnp.sum(f0)))
    vo_ref[...] = v
    tho_ref[...] = theta
    fp_ref[...] = f


# VMEM of the coupled body (train_window_batch_encode with θ), bytes:
# the packed weights and LFSR lanes in and out, double-buffered, the
# resident int8 weights, the block's int32 currents and int8 spike tile,
# the raster block (double-buffered; a bool takes 4 bytes in VMEM) and
# one group's int32 matmul result.  At 784 x 6,400 (K 896, 128 words)
# and 352 cycles: 26.2 + 5.7 + 9.0 + 0.3 + 18.0 + 0.2 MB = 59.4 MB.
_THETA_VMEM_LIMIT = 100 << 20   # of the v5e core's 128 MiB of VMEM
_THETA_VMEM_USE = _THETA_VMEM_LIMIT * 3 // 4   # room for the compiler's


def _coupled_vmem_bytes(n: int, words: int, kk: int, t_chunk: int) -> int:
    """The coupled body's VMEM at n neurons (a multiple of 128), ``words``
    packed words, K = ``kk`` inputs and ``t_chunk``-cycle blocks."""
    return (32 * n * words + kk * n + 4 * t_chunk * n + t_chunk * kk
            + 8 * t_chunk * n + 4 * t_chunk * 128)


def _coupled_chunk(n_steps: int, n: int, words: int, kk: int) -> int:
    """The coupled body's default block: whole int8 tiles of 32 cycles,
    within :data:`_THETA_VMEM_USE`, with the fewest padded cycles (then
    the longest block, which loads each weight tile the fewest times)."""
    top = -(-n_steps // 32) * 32
    fits = [c for c in range(32, top + 1, 32)
            if _coupled_vmem_bytes(n, words, kk, c) <= _THETA_VMEM_USE]
    return min(fits or [32], key=lambda c: (-(-n_steps // c) * c, -c))


def train_window_batch_encode(weights, intens_words, seeds, v, lfsr_state,
                              teach, *, n_steps: int, threshold: int,
                              leak: int, w_exp: int, gain: int,
                              n_syn: int, ltp_prob, block_n=128,
                              t_chunk: int | None = None, theta=None,
                              intensities=None, w_inh: int = 0,
                              theta_plus: int = 0, interpret=False):
    """B training streams whose spike windows are generated in VMEM.

    Same grid/carry scheme as :func:`train_window_batch`, but the spike
    slab operand is replaced by intensity words u32[B, 8, w] (byte
    layout of :func:`_encode_cycle`) plus per-stream counter seeds
    i32[B] — each cycle's packed row is drawn on the fly, so the input
    stream shrinks from ``T*w*4`` to ``n_in`` bytes per stream and the
    draw is identical across chunkings (the hash is keyed on the
    absolute cycle).  Bit-exact with :func:`train_window_batch` fed the
    ``encoder.encode_from_counter`` host windows.

    ``theta`` i32[B, n] (adaptive thresholds; n a multiple of 128)
    selects the body of :func:`_train_window_enc_coupled`, with
    ``w_inh`` and ``theta_plus`` as trace-time literals: one neuron
    block holds each stream's whole population (``block_n`` is not
    used), its currents computed on the MXU a block of ``t_chunk``
    cycles at a time from a resident int8 copy of the weights, with
    ``intensities`` i32[B, K] (input ``i`` in lane ``i``, zero past
    n_in; K a multiple of 128) for the dense spike tile.  ``t_chunk``
    defaults to :func:`_coupled_chunk`'s; on the chip it is a multiple
    of 32 (the int8 tile's rows).  θ' and the recompute count i32[B]
    are then a fifth and a sixth result.

    Returns (weights', v', fired bool[B, T_pad, n], lfsr'[, theta',
    recomputes]) with T_pad = n_steps rounded up to the chunk (callers
    slice to n_steps).
    """
    b, n, w = weights.shape
    lp = jnp.asarray(ltp_prob, jnp.int32)
    if lp.ndim == 0:
        lp = jnp.broadcast_to(lp, (b,))
    sd = jnp.broadcast_to(jnp.asarray(seeds, jnp.int32), (b,))
    if theta is None:
        if w_inh or theta_plus:
            raise ValueError("w_inh and theta_plus need the theta operand")
        tc, t_pad = _t_grid(n_steps, t_chunk)
        kern = functools.partial(_train_window_enc_kernel, int(threshold),
                                 int(leak), w_exp, gain, n_syn, tc,
                                 int(n_steps))
        w2, v2, fired, s2 = pl.pallas_call(
            kern,
            out_shape=(jax.ShapeDtypeStruct((b, n, w), jnp.uint32),
                       jax.ShapeDtypeStruct((b, 1, n), jnp.int32),
                       jax.ShapeDtypeStruct((b, t_pad, n), jnp.bool_),
                       jax.ShapeDtypeStruct((b, n, w), jnp.uint32)),
            grid=(n // block_n, b, t_pad // tc),
            in_specs=[
                _SMEM_WHOLE,
                _SMEM_WHOLE,
                _state_block(block_n, w),
                _intens_block(w),
                _row_block(block_n),
                _state_block(block_n, w),
                _row_block(block_n),
            ],
            out_specs=(
                _state_block(block_n, w),
                _row_block(block_n),
                _raster_block(tc, block_n),
                _state_block(block_n, w),
            ),
            interpret=interpret,
            name="train_window_batch_encode",
        )(lp, sd, weights, intens_words, v[:, None], lfsr_state,
          teach[:, None])
        return w2, v2[:, 0], fired, s2
    lanes = 128
    if n % lanes:
        raise ValueError(f"the population n={n} must be a multiple of "
                         f"{lanes}")
    g = n // lanes
    kk = intensities.shape[-1]
    tc = _coupled_chunk(n_steps, n, w, kk) if t_chunk is None else t_chunk
    t_pad = -(-n_steps // tc) * tc
    kern = functools.partial(_train_window_enc_coupled, int(threshold),
                             int(leak), w_exp, gain, n_syn, tc,
                             int(n_steps), int(w_inh), int(theta_plus))
    state = pl.BlockSpec((None, n, w), lambda i, j, k: (j, 0, 0))
    row = pl.BlockSpec((None, g, lanes), lambda i, j, k: (j, 0, 0))
    one = pl.BlockSpec((None, 1, kk), lambda i, j, k: (j, 0, 0))
    count = pl.BlockSpec((None, 1, lanes), lambda i, j, k: (j, 0, 0))
    w2, v2, fired, s2, th2, rc = pl.pallas_call(
        kern,
        out_shape=(jax.ShapeDtypeStruct((b, n, w), jnp.uint32),
                   jax.ShapeDtypeStruct((b, g, lanes), jnp.int32),
                   jax.ShapeDtypeStruct((b, t_pad, g, lanes), jnp.bool_),
                   jax.ShapeDtypeStruct((b, n, w), jnp.uint32),
                   jax.ShapeDtypeStruct((b, g, lanes), jnp.int32),
                   jax.ShapeDtypeStruct((b, 1, lanes), jnp.int32)),
        grid=(1, b, t_pad // tc),
        in_specs=[_SMEM_WHOLE, _SMEM_WHOLE, state, _intens_block(w), one,
                  row, state, row, row],
        out_specs=(state, row,
                   pl.BlockSpec((None, tc, g, lanes),
                                lambda i, j, k: (j, k, 0, 0)),
                   state, row, count),
        scratch_shapes=[pltpu.VMEM((g, lanes), jnp.int32),
                        pltpu.VMEM((g, kk, lanes), jnp.int8),
                        pltpu.VMEM((g * tc, lanes), jnp.int32),
                        pltpu.VMEM((tc, kk), jnp.int8)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_THETA_VMEM_LIMIT),
        interpret=interpret,
        name="train_window_batch_encode",
    )(lp, sd, weights, intens_words, intensities[:, None],
      v.reshape(b, g, lanes), lfsr_state, teach.reshape(b, g, lanes),
      theta.reshape(b, g, lanes))
    return (w2, v2.reshape(b, n), fired.reshape(b, t_pad, n), s2,
            th2.reshape(b, n), rc[:, 0, 0])


def fused_snn_window_encode(weights, intens_words, seed, v, lfsr_state,
                            teach, *, n_steps: int, threshold: int,
                            leak: int, w_exp: int, gain: int, n_syn: int,
                            ltp_prob: int, train: bool = True,
                            block_n=128, t_chunk: int | None = None,
                            interpret=False):
    """One stream, T cycles, spikes generated in VMEM (B=1 of the
    batched encode grid; ``train=False`` uses a read-only variant as in
    :func:`fused_snn_window`).

    intens_words u32[8, w], seed i32 scalar.  Returns
    (weights', v', fired bool[T_pad, n], lfsr').
    """
    n, w = weights.shape
    tc, t_pad = _t_grid(n_steps, t_chunk)
    if not train:
        sd = jnp.reshape(jnp.asarray(seed, jnp.int32), (1,))
        v2, fired = pl.pallas_call(
            functools.partial(_window_infer_enc_kernel, int(threshold),
                              int(leak), tc, int(n_steps)),
            out_shape=(jax.ShapeDtypeStruct((n,), jnp.int32),
                       jax.ShapeDtypeStruct((t_pad, n), jnp.bool_)),
            grid=(n // block_n, t_pad // tc),
            in_specs=[
                _SMEM_WHOLE,
                pl.BlockSpec((block_n, w), lambda i, k: (i, 0)),
                pl.BlockSpec((8, w), lambda i, k: (0, 0)),
                pl.BlockSpec((block_n,), lambda i, k: (i,)),
                pl.BlockSpec((block_n,), lambda i, k: (i,)),
            ],
            out_specs=(pl.BlockSpec((block_n,), lambda i, k: (i,)),
                       pl.BlockSpec((tc, block_n), lambda i, k: (k, i))),
            interpret=interpret,
        )(sd, weights, intens_words, v, teach)
        return weights, v2, fired, lfsr_state
    w2, v2, fired, s2 = train_window_batch_encode(
        weights[None], intens_words[None], jnp.asarray(seed, jnp.int32),
        v[None], lfsr_state[None], teach[None], n_steps=n_steps,
        threshold=threshold, leak=leak, w_exp=w_exp, gain=gain,
        n_syn=n_syn, ltp_prob=ltp_prob, block_n=block_n, t_chunk=tc,
        interpret=interpret)
    return w2[0], v2[0], fired[0], s2[0]


# --- serving on the MXU: frozen weights make the input currents a matmul ----
# A cycle's input count popcount(pre & w) does not depend on the membrane
# once the weights are frozen, so the counts of a block of samples over a
# block of cycles are one integer matmul (spikes[rows, K] @ w[K, BN], the
# products 0/1 and the sums at most n_in, exact in int8 x int8 -> int32);
# an elementwise LIF scan over the cycles follows.  The spike tile of a
# (sample block, time chunk) is drawn once, at its first neuron block,
# and reused from VMEM by every other neuron block.

_MXU_ROWS = 512              # spike-tile rows (cycles x samples) per matmul
_SPIKE_TILE_BYTES = 4 << 20  # VMEM cap of the int8 spike tile


def _encode_tile(seed, cycle, inten):
    """One cycle's spikes for a block of samples as a dense 0/1 tile.

    seed u32[bb, 1], inten u32[bb, K] with input ``i`` in lane ``i``
    (zero past n_in).  Entry (b, i) is 1 iff ``counter_hash(seed_b,
    cycle, i) & 0xFF < inten[b, i]``: the draw of :func:`_encode_cycle`,
    one input per lane instead of one per bit.
    """
    idx = jax.lax.broadcasted_iota(jnp.uint32, (1, inten.shape[1]), 1)
    h = _counter_hash(seed, cycle, idx)
    return (jnp.bitwise_and(h, jnp.uint32(0xFF)) < inten).astype(jnp.int8)


def _unpack_weights(w, k):
    """Packed u32[bn, Wp] weights -> 0/1 int8[k, bn], input ``i`` in row
    ``i`` (bit i % 32 of word i // 32), ready as the matmul's right side."""
    wt = w.T                                    # (Wp, bn): words on sublanes
    bn = wt.shape[1]
    shift = jax.lax.broadcasted_iota(jnp.uint32, (32, bn), 0)
    rows = [jnp.bitwise_and(
        jnp.right_shift(jnp.broadcast_to(wt[q:q + 1, :], (32, bn)), shift),
        jnp.uint32(1)) for q in range(k // 32)]
    return jnp.concatenate(rows, axis=0).astype(jnp.int8)


def _sample_column(ref, start, bb):
    """SMEM scalars ref[start : start + bb] as an i32[bb, 1] column."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (bb, 128), 0)
    col = jnp.zeros((bb, 128), jnp.int32)
    for r in range(bb):
        col = jnp.where(rows == r, ref[start + r], col)
    return col[:, :1]


def _infer_window_mxu_kernel(threshold, leak, t_chunk,
                             seed_ref, tt_ref, w_ref, x_ref, o_ref,
                             s_ref, v_ref, acc_ref):
    i, k, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    bb, kk = x_ref.shape
    bn = o_ref.shape[1]
    base = k * t_chunk

    @pl.when(j == 0)
    def _draw():
        seed = _sample_column(seed_ref, i * bb, bb).astype(jnp.uint32)
        inten = x_ref[...].astype(jnp.uint32)

        def cycle(t, carry):
            row = pl.multiple_of(t * bb, bb)
            s_ref[pl.ds(row, bb), :] = _encode_tile(
                seed, (base + t).astype(jnp.uint32), inten)
            return carry

        jax.lax.fori_loop(0, t_chunk, cycle, 0)

    @pl.when(k == 0)
    def _reset():
        v_ref[j] = jnp.zeros((bb, bn), jnp.int32)
        acc_ref[j] = jnp.zeros((bb, bn), jnp.int32)

    w = _unpack_weights(w_ref[...], kk)
    # per-SAMPLE window length (a traced operand, not a literal): one
    # launch serves a ragged batch, freezing each row past its t_total
    tt = jnp.broadcast_to(_sample_column(tt_ref, i * bb, bb), (bb, bn))

    def cycles(t0, n, carry):
        """LIF over cycles t0 .. t0+n-1 of the chunk (n static)."""
        v, acc = carry
        cur = jnp.dot(s_ref[pl.ds(t0 * bb, n * bb), :], w,
                      preferred_element_type=jnp.int32)
        for u in range(n):
            v_int = v + cur[u * bb:(u + 1) * bb]
            fired = v_int >= threshold
            v_next = jnp.where(fired, jnp.int32(0),
                               jnp.maximum(v_int - leak, jnp.int32(0)))
            active = base + t0 + u < tt
            acc = acc + jnp.logical_and(fired, active).astype(jnp.int32)
            v = jnp.where(active, v_next, v)
        return v, acc

    group = max(1, min(t_chunk, _MXU_ROWS // bb))
    n_groups, tail = divmod(t_chunk, group)
    carry = (v_ref[j], acc_ref[j])
    if n_groups:
        carry = jax.lax.fori_loop(
            0, n_groups,
            lambda g, c: cycles(pl.multiple_of(g * group, group), group, c),
            carry)
    if tail:
        carry = cycles(n_groups * group, tail, carry)
    v_ref[j], acc_ref[j] = carry
    o_ref[...] = carry[1]


def infer_window_batch_encode(weights, intensities, seeds, t_totals, *,
                              n_steps: int, threshold: int, leak: int,
                              block_b: int = 32, block_n: int = 128,
                              t_chunk: int | None = None, interpret=False):
    """Serving kernel, intensity-resident: B windows generated in VMEM,
    their input currents computed on the MXU.

    weights u32[n, Wp] packed; intensities i32[B, K] with input ``i`` in
    column ``i`` (zero past n_in; K a multiple of 128 and at most 32 *
    Wp); seeds, t_totals i32[B] — each sample's counter base and window
    length, whole SMEM operands.  The length is a traced operand, NOT a
    literal, so one launch serves a ragged batch: sample b's cycles at
    or past ``t_totals[b]`` count no spikes and advance no state.
    Requires B % block_b == 0 (block_b a multiple of 32) and n % block_n
    == 0.

    Grid (sample block, time chunk, neuron block), neuron block
    innermost: each (sample block, chunk) draws its spike tile once, and
    each sample block carries v and the counts of every neuron block
    across chunks in VMEM.  ``t_chunk`` (default: the window, capped so
    the spike tile stays under 4 MiB) bounds VMEM and changes no result.
    Bit-exact with :func:`infer_window_batch` fed host-encoded (and
    zero-masked) windows.  Returns spike counts int32[B, n].
    """
    n, wp = weights.shape
    b, kk = intensities.shape
    if t_chunk is None:
        t_chunk = max(1, _SPIKE_TILE_BYTES // (block_b * kk))
    tc, t_pad = _t_grid(n_steps, t_chunk)
    nb = n // block_n
    kern = functools.partial(_infer_window_mxu_kernel, int(threshold),
                             int(leak), tc)
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((b, n), jnp.int32),
        grid=(b // block_b, t_pad // tc, nb),
        in_specs=[
            _SMEM_WHOLE,
            _SMEM_WHOLE,
            pl.BlockSpec((block_n, wp), lambda i, k, j: (j, 0)),
            pl.BlockSpec((block_b, kk), lambda i, k, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, block_n), lambda i, k, j: (i, j)),
        scratch_shapes=[
            pltpu.VMEM((tc * block_b, kk), jnp.int8),
            pltpu.VMEM((nb, block_b, block_n), jnp.int32),
            pltpu.VMEM((nb, block_b, block_n), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="infer_window_batch_encode",
    )(seeds, t_totals, weights, intensities)
