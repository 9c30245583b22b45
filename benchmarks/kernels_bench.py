"""RV-SNN granularity claim: fused SNNU step vs unfused SPU->NU->SU,
and the time axis on top: the window kernel vs T per-step launches.

The paper's coarse-grained instruction avoids pipeline stalls; the TPU
analogue is HBM round-trips between kernel launches.  We report (a)
wall time per call across population sizes (relative only — CPU
emulation of the ref/XLA paths, plus one small interpret-mode row that
exercises the actual Pallas kernel body), and (b) the structural metric
that transfers to TPU: analytic minimum HBM bytes per call.

Four levels of scale-out, each vs its sequential baseline:
  * fused step vs unfused SPU->NU->SU chain (one cycle, 3 launches);
  * fused window vs T fused-step launches (the whole presentation
    window, weights/LFSR resident in VMEM — weight traffic drops ~T×);
  * batched training grid vs B sequential window launches (one launch
    trains B independent streams);
  * neuron-sharded window ops vs single-core (per-device weight
    traffic drops D× on a D-device mesh; run.py forces an 8-device
    host mesh so the shard_map path really executes here).
Plus chunked spike streaming: the VMEM spike slab shrinks T/T_chunk×
while staying bit-exact, which is what lets T grow unbounded.
"""

from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

_REPO = Path(__file__).resolve().parents[1]

from benchmarks.common import emit, time_fn
from repro.core import lfsr
from repro.kernels import ops

KW = dict(threshold=192, leak=16, w_exp=128, gain=4, ltp_prob=16)


def _operands(n, w, seed=0):
    rng = np.random.default_rng(seed)
    weights = jnp.asarray(rng.integers(0, 2**32, (n, w), dtype=np.uint32))
    pre = jnp.asarray(rng.integers(0, 2**32, (w,), dtype=np.uint32))
    v = jnp.zeros((n,), jnp.int32)
    teach = jnp.zeros((n,), jnp.int32)
    st = lfsr.seed(1, n * w).reshape(n, w)
    return weights, pre, v, st, teach


def _mesh_bench(argv: list[str], marker: str) -> dict | None:
    """Run ``repro.distributed.snn_mesh --bench`` in a child process on
    a forced 8-device CPU mesh (the forced mesh would split this
    process's thread pool and skew every other wall-clock row) and
    return the ``marker`` line's key=value fields, None when it failed.

    A child cannot reach an accelerator this process already holds, so
    off the CPU the row fails loudly instead of timing something else.
    """
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            f"the {marker} row forks a forced-CPU-mesh child and only runs "
            f"on the CPU; this process holds {jax.default_backend()}")
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8"
                        ).strip()
    env["PYTHONPATH"] = (str(_REPO / "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "repro.distributed.snn_mesh", "--bench",
             *argv], env=env, capture_output=True, text=True, timeout=600)
    except subprocess.TimeoutExpired as e:
        proc = subprocess.CompletedProcess(e.cmd, -1, stdout="",
                                           stderr="timeout after 600s")
    row = next((ln for ln in proc.stdout.splitlines()
                if ln.startswith(marker + " ")), None)
    if proc.returncode != 0 or row is None:
        print(f"# {marker} row skipped "
              f"(rc={proc.returncode}): {proc.stderr.strip()[:200]}")
        return None
    return dict(p.split("=", 1) for p in row.split()[1:])


def run() -> dict:
    out = {}
    for n, w in ((256, 32), (1024, 64), (4096, 256)):
        n_syn = w * 32
        weights, pre, v, st, teach = _operands(n, w)

        fused = jax.jit(lambda *a: ops.fused_snn_step(
            *a, n_syn=n_syn, **KW))

        # the unfused path is THREE separate kernel launches (the
        # fine-grained instruction sequence): each round-trips HBM
        spu = jax.jit(lambda p, wt: ops.spike_process(p, wt))
        nu = jax.jit(lambda vv, cc: ops.lif_step(
            vv, cc, KW["threshold"], KW["leak"]))
        su = jax.jit(lambda wt, p, f, s: ops.stdp_update(
            wt, p, f, s, w_exp=KW["w_exp"], gain=KW["gain"],
            n_syn=n_syn, ltp_prob=KW["ltp_prob"]))

        def unfused_chain(weights, pre, v, st, teach):
            counts = spu(pre, weights)
            v2, fired = nu(v, counts + teach)
            w2, s2 = su(weights, pre, fired, st)
            return w2, v2, fired, s2

        t_f = time_fn(fused, weights, pre, v, st, teach, reps=5)
        t_u = time_fn(unfused_chain, weights, pre, v, st, teach, reps=5)

        # analytic minimum HBM traffic per step (bytes):
        #   fused:   W r+w, LFSR r+w, spikes r          (one VMEM pass)
        #   unfused: W r(SPU)+r+w(SU), LFSR r+w, spikes r(SPU)+r(SU),
        #            counts w+r, V r+w, fired w+r       (3 launches)
        wb = n * w * 4
        sb = w * 4
        nb = n * 4
        b_f = 2 * wb + 2 * wb + sb            # W rw + LFSR rw + spikes
        b_u = 3 * wb + 2 * wb + 2 * sb + 2 * nb + 2 * nb + 2 * n
        emit(f"kernels/fused-{n}x{n_syn}", t_f,
             f"min_hbm_bytes={b_f}")
        emit(f"kernels/unfused-{n}x{n_syn}", t_u,
             f"min_hbm_bytes={b_u};bytes_ratio={b_u/b_f:.2f}x;"
             f"time_ratio={t_u/max(t_f,1e-9):.2f}x")
        out[(n, n_syn)] = {"bytes_ratio": b_u / b_f,
                           "time_ratio": t_u / max(t_f, 1e-9)}

    # --- time axis: window kernel vs T per-step fused launches ----------
    rng = np.random.default_rng(7)
    for n, w, t_steps in ((256, 32, 72), (1024, 64, 32), (1024, 64, 128)):
        n_syn = w * 32
        weights, _, v, st, teach = _operands(n, w)
        spk = jnp.asarray(
            rng.integers(0, 2**32, (t_steps, w), dtype=np.uint32))

        window = jax.jit(lambda *a: ops.fused_snn_window(
            *a, n_syn=n_syn, **KW))

        # the per-step path is T SEPARATE launches (one dispatch per
        # cycle, state round-tripping host-visible buffers between
        # them) — jitting a scan over the steps would fuse them into
        # the very program the window op builds, measuring nothing
        step = jax.jit(lambda *a: ops.fused_snn_step(
            *a, n_syn=n_syn, **KW))

        def step_chain(weights, spk, v, st, teach):
            for t in range(spk.shape[0]):
                weights, v, f, st = step(weights, spk[t], v, st, teach)
            return weights, v, st

        t_w = time_fn(window, weights, spk, v, st, teach, reps=5)
        t_s = time_fn(step_chain, weights, spk, v, st, teach, reps=5)

        # analytic minimum HBM traffic per window (bytes):
        #   per-step: every launch round-trips weights + LFSR and reads
        #             its spike row           -> T * (4*wb + sb)
        #   window:   weights + LFSR cross HBM once, the T spike rows
        #             stream in, the raster + v stream out
        wb = n * w * 4
        sb = w * 4
        nb = n * 4
        b_steps = t_steps * (4 * wb + sb)
        b_win = 4 * wb + t_steps * sb + t_steps * n + 2 * nb
        emit(f"kernels/window-{n}x{n_syn}xT{t_steps}", t_w,
             f"min_hbm_bytes={b_win};bytes_ratio={b_steps/b_win:.2f}x;"
             f"time_ratio={t_s/max(t_w,1e-9):.2f}x")
        out[(n, n_syn, t_steps)] = {"bytes_ratio": b_steps / b_win,
                                    "time_ratio": t_s / max(t_w, 1e-9)}

    # --- batch axis: batched training grid vs B sequential windows ------
    for n, w, t_steps, b in ((16, 25, 72, 8), (128, 32, 32, 8)):
        n_syn = w * 32
        rngb = np.random.default_rng(11)
        wts = jnp.asarray(
            rngb.integers(0, 2**32, (b, n, w), dtype=np.uint32))
        spk = jnp.asarray(
            rngb.integers(0, 2**32, (b, t_steps, w), dtype=np.uint32))
        v = jnp.zeros((b, n), jnp.int32)
        teach = jnp.zeros((b, n), jnp.int32)
        st = jnp.stack([lfsr.seed(1 + i, n * w).reshape(n, w)
                        for i in range(b)])

        batched = jax.jit(lambda *a: ops.train_window_batch(
            *a, n_syn=n_syn, **KW))
        window = jax.jit(lambda *a: ops.fused_snn_window(
            *a, n_syn=n_syn, **KW))

        # the sequential baseline is B SEPARATE window launches — one
        # per training stream, exactly what the pre-batch trainer did
        # per active-learning block / epoch replica
        def seq_chain(wts, spk, v, st, teach):
            outs = []
            for i in range(b):
                outs.append(window(wts[i], spk[i], v[i], st[i],
                                   teach[i]))
            return outs

        t_b = time_fn(batched, wts, spk, v, st, teach, reps=5)
        t_q = time_fn(seq_chain, wts, spk, v, st, teach, reps=5)
        emit(f"kernels/train-batch-{n}x{n_syn}xT{t_steps}xB{b}", t_b,
             f"launches=1_vs_{b};"
             f"time_ratio={t_q/max(t_b,1e-9):.2f}x")
        out[("train_batch", n, n_syn, t_steps, b)] = {
            "time_ratio": t_q / max(t_b, 1e-9)}

    # --- neuron axis: sharded window ops vs single-core -----------------
    ndev = 8
    n, w, t_steps, b = 1024, 64, 32, 8
    n_syn = w * 32
    kv = _mesh_bench(["--devices", str(ndev), "--neurons", str(n),
                      "--words", str(w), "--steps", str(t_steps),
                      "--batch", str(b)], "BENCH")
    if kv is not None:
        t_1, t_d = float(kv["t_single_us"]), float(kv["t_shard_us"])
        # analytic per-device weight traffic: each device reads only its
        # n/D rows once per launch — the capacity metric that lets
        # populations scale past one core's VMEM
        wb = n * w * 4
        emit(f"kernels/window-shard-{n}x{n_syn}xD{ndev}", t_d,
             f"per_device_weight_bytes={wb // ndev};"
             f"bytes_ratio={ndev:.2f}x;"
             f"time_ratio={t_1/max(t_d,1e-9):.2f}x")
        out[("shard", n, n_syn, ndev)] = {
            "bytes_ratio": float(ndev),
            "time_ratio": t_1 / max(t_d, 1e-9)}

    # --- on-core encode: intensity stream vs pre-packed spike windows ---
    # The serving input shrinks from the T*w*4-byte packed window to the
    # n_in uint8 intensities it was generated from (bytes_ratio = T/8 —
    # the encode-fused kernel draws each cycle's spikes in VMEM).  Wall
    # clock compares end-to-end from intensities: host counter-encode +
    # pre-packed launch vs the single encode-fused launch (both XLA-ref
    # on CPU; the structural metric that transfers to TPU is the bytes).
    from repro.core.encoder import encode_from_counter_batch

    b = 8
    for n, w, t_steps in ((1024, 64, 32), (1024, 64, 128)):
        n_in = w * 32
        rng_e = np.random.default_rng(13)
        weights = jnp.asarray(
            rng_e.integers(0, 2**32, (n, w), dtype=np.uint32))
        inten = jnp.asarray(
            rng_e.integers(0, 256, (b, n_in), dtype=np.uint8))
        seeds = jnp.arange(1, b + 1, dtype=jnp.int32)

        pre = jax.jit(lambda wt, x, s, t=t_steps: ops.infer_window_batch(
            wt, encode_from_counter_batch(s, x, t),
            threshold=KW["threshold"], leak=KW["leak"]))
        enc = jax.jit(
            lambda wt, x, s, t=t_steps: ops.infer_window_batch_encode(
                wt, x, s, n_steps=t, threshold=KW["threshold"],
                leak=KW["leak"]))

        t_pre = time_fn(pre, weights, inten, seeds, reps=5)
        t_enc = time_fn(enc, weights, inten, seeds, reps=5)
        in_pre = t_steps * w * 4           # packed window bytes/sample
        in_enc = n_in                      # uint8 intensity bytes/sample
        emit(f"kernels/encode-{n}x{n_in}xT{t_steps}", t_enc,
             f"input_bytes={in_enc};bytes_ratio={in_pre/in_enc:.2f}x;"
             f"time_ratio={t_pre/max(t_enc,1e-9):.2f}x")
        out[("encode", n, n_in, t_steps)] = {
            "bytes_ratio": in_pre / in_enc,
            "time_ratio": t_pre / max(t_enc, 1e-9)}

    # --- intensity-resident training: dataset bytes vs host pre-encode --
    # The trainer's ingestion claim: with encode="kernel" the dataset
    # stays n_in uint8 bytes/sample instead of the T*w*4-byte pre-packed
    # window.  The ratio here is analytic (a function of the row's
    # shape, >= 8x at T=128; the assert only pins the shape choice) —
    # the guarantee that trainer.train really never materializes the
    # N×T×w tensor is tests/test_train_ingest.py's monkeypatch test.
    # Wall clock compares end-to-end from intensities: host
    # counter-encode + pre-packed batched training vs the single
    # encode-fused training launch.
    from repro.core.encoder import encode_from_counter_batch as _efc

    b = 32
    for n, w, t_steps in ((256, 25, 128),):
        n_in = w * 32
        n_syn = n_in
        rng_t = np.random.default_rng(17)
        wts = jnp.asarray(
            rng_t.integers(0, 2**32, (b, n, w), dtype=np.uint32))
        inten = jnp.asarray(
            rng_t.integers(0, 256, (b, n_in), dtype=np.uint8))
        seeds = jnp.arange(1, b + 1, dtype=jnp.int32)
        v = jnp.zeros((b, n), jnp.int32)
        teach = jnp.zeros((b, n), jnp.int32)
        st = jnp.stack([lfsr.seed(1 + i, n * w).reshape(n, w)
                        for i in range(b)])

        pre = jax.jit(lambda wt, x, s, vv, lf, tc, t=t_steps:
                      ops.train_window_batch(
                          wt, _efc(s, x, t), vv, lf, tc, n_syn=n_syn,
                          **KW))
        enc = jax.jit(lambda wt, x, s, vv, lf, tc, t=t_steps:
                      ops.train_window_batch_encode(
                          wt, x, s, vv, lf, tc, n_steps=t, n_syn=n_syn,
                          **KW))

        t_pre = time_fn(pre, wts, inten, seeds, v, st, teach, reps=5)
        t_enc = time_fn(enc, wts, inten, seeds, v, st, teach, reps=5)
        ds_pre = t_steps * w * 4           # pre-packed window bytes/sample
        ds_int = n_in                      # uint8 intensity bytes/sample
        assert ds_pre / ds_int >= 8.0, (
            f"dataset-bytes reduction collapsed: {ds_pre}/{ds_int}")
        emit(f"kernels/train-intensity-{n}x{n_in}xT{t_steps}xB{b}",
             t_enc,
             f"dataset_bytes={ds_int};bytes_ratio={ds_pre/ds_int:.2f}x;"
             f"time_ratio={t_pre/max(t_enc,1e-9):.2f}x")
        out[("train-intensity", n, n_in, t_steps, b)] = {
            "bytes_ratio": ds_pre / ds_int,
            "time_ratio": t_pre / max(t_enc, 1e-9)}

    # --- 2-D (data × neuron) mesh: the batched training grid sharded
    # over BOTH axes vs the 1-D neuron mesh (same 8 devices).
    d2, n2 = 2, 4
    n, w, t_steps, b = 1024, 64, 32, 32
    n_syn = w * 32
    kv = _mesh_bench(["--mesh-shape", f"{d2},{n2}", "--neurons", str(n),
                      "--words", str(w), "--steps", str(t_steps),
                      "--batch", str(b)], "BENCH2D")
    if kv is not None:
        t_1d, t_2d = float(kv["t_1d_us"]), float(kv["t_2d_us"])
        # structural per-device metrics: a (d, n) grid gives each device
        # b/d streams × 1/n of every regfile — weight traffic drops
        # d*n x vs single-device, and d x vs the 1-D neuron mesh that
        # replicates all b streams' windows everywhere
        emit(f"kernels/train-2d-{n}x{n_syn}xT{t_steps}xB{b}", t_2d,
             f"mesh={d2}x{n2};streams_per_device={b // d2};"
             f"bytes_ratio={float(d2):.2f}x;"
             f"time_ratio={t_1d/max(t_2d,1e-9):.2f}x")
        out[("train-2d", n, n_syn, t_steps, b)] = {
            "bytes_ratio": float(d2),
            "time_ratio": t_1d / max(t_2d, 1e-9)}

    # analytic streaming extreme: at T=2048 the pre-packed input stream
    # is 256x the intensity bytes (and the encode kernel's VMEM holds no
    # spike slab at all) — analytic-only, nothing is timed
    n_in = 64 * 32
    emit(f"kernels/encode-stream-1024x{n_in}xT2048", None,
         f"input_bytes={n_in};"
         f"bytes_ratio={2048 * 64 * 4 / n_in:.2f}x")
    out[("encode-stream", 1024, n_in, 2048)] = {
        "bytes_ratio": 2048 * 64 * 4 / n_in}

    # --- chunked spike streaming: bounded VMEM at unbounded T -----------
    # (analytic: the streamed slab is the only T-dependent VMEM term)
    for n, w, t_steps, tc in ((1024, 64, 2048, 64),):
        slab_full = t_steps * w * 4
        slab_chunk = tc * w * 4
        emit(f"kernels/window-chunk-{n}x{w * 32}xT{t_steps}c{tc}", None,
             f"vmem_spike_bytes={slab_chunk};"
             f"vmem_ratio={slab_full/slab_chunk:.2f}x")
        out[("chunk", n, t_steps, tc)] = {
            "vmem_ratio": slab_full / slab_chunk}

    # one small interpret-mode row: the real Pallas window-kernel body
    # (Python-interpreted, so absolute time is meaningless; it documents
    # that the kernel itself runs and how it scales vs the oracle),
    # exercised in chunked form (T=8 in two 4-cycle slabs)
    n, w, t_steps = 16, 4, 8
    weights, _, v, st, teach = _operands(n, w, seed=3)
    spk = jnp.asarray(rng.integers(0, 2**32, (t_steps, w), dtype=np.uint32))
    t_i = time_fn(
        lambda *a: ops.fused_snn_window(*a, n_syn=w * 32, backend="interp",
                                        t_chunk=4, **KW),
        weights, spk, v, st, teach, reps=3, warmup=1)
    emit(f"kernels/window-interp-{n}x{w * 32}xT{t_steps}c4", t_i,
         "backend=interp")

    # ...and the encode-fused serving kernel body (interpret mode,
    # chunked, ragged lengths) — documents the in-VMEM draw itself runs
    inten_i = jnp.asarray(rng.integers(0, 256, (2, w * 32),
                                       dtype=np.uint8))
    t_ie = time_fn(
        lambda *a: ops.infer_window_batch_encode(
            *a, n_steps=t_steps, threshold=KW["threshold"],
            leak=KW["leak"], t_total=jnp.asarray([t_steps, t_steps - 3]),
            t_chunk=4, backend="interp"),
        weights, inten_i, jnp.asarray([1, 2], jnp.int32),
        reps=3, warmup=1)
    emit(f"kernels/encode-interp-{n}x{w * 32}xT{t_steps}c4", t_ie,
         "backend=interp")

    # --- serving latency: queue-wait + service percentiles --------------
    # End-to-end request latency through the dynamic-window-batching
    # SNNServingEngine (intensity requests, ragged T's — the same path
    # ``serve --bench`` reports).  One throwaway pass warms every
    # window-length bucket's compile cache, then the latency lists are
    # cleared so the measured pass sees steady-state serving only.  The
    # percentiles land in BENCH_kernels.json as the committed baseline;
    # run.py --gate fails when a percentile grows past
    # GATE_LATENCY_RATIO x its baseline above an absolute floor — the
    # increase direction, unlike the kernel speedup ratios which gate
    # on drops.
    from repro.engine import SNNEnginePlan
    from repro.serving import SNNRequest, SNNServingEngine

    n_req, n, w, t_steps = 32, 64, 8, 16
    rng_l = np.random.default_rng(21)
    s_weights = np.asarray(
        rng_l.integers(0, 2**32, (n, w), dtype=np.uint32))
    s_inten = rng_l.integers(0, 256, (n_req, w * 32), dtype=np.uint8)
    plan_l = SNNEnginePlan(threshold=192, leak=16, n_syn=w * 32,
                           encode="kernel", cycle_backend="window",
                           max_batch=8, t_chunk=8)

    def _latency_reqs(base):
        return [SNNRequest(rid=base + i, intensities=s_inten[i],
                           n_steps=t_steps - 4 * (i % 3))
                for i in range(n_req)]

    s_eng = SNNServingEngine(s_weights, plan_l)
    s_eng.run(_latency_reqs(0))            # warm all T-bucket compiles
    s_eng.queue_wait_hist.reset()
    s_eng.service_hist.reset()
    s_eng.run(_latency_reqs(n_req))        # measured steady-state pass
    s_st = s_eng.stats()
    lat_keys = ("queue_wait_ms_p50", "queue_wait_ms_p99",
                "service_ms_p50", "service_ms_p99")
    emit(f"serve/latency-{n}x{w * 32}xT{t_steps}r{n_req}", None,
         ";".join(f"{k}={s_st[k]:.3f}" for k in lat_keys))
    out[("serve-latency", n, w * 32, t_steps, n_req)] = {
        k: s_st[k] for k in lat_keys}

    # ...and the same pass with the crash-consistency journal enabled
    # (fsync'd WAL + periodic snapshots): documents the durability
    # overhead and gates it with the same increase-direction latency
    # rule, so journaling can never silently blow the serving budget
    import shutil
    import tempfile

    jdir = tempfile.mkdtemp(prefix="bench-journal-")
    try:
        j_eng = SNNServingEngine(s_weights, plan_l, journal_dir=jdir,
                                 snapshot_every=4)
        j_eng.run(_latency_reqs(0))        # warm all T-bucket compiles
        j_eng.queue_wait_hist.reset()
        j_eng.service_hist.reset()
        j_eng.run(_latency_reqs(n_req))    # measured steady-state pass
        j_st = j_eng.stats()
        j_eng.close()
    finally:
        shutil.rmtree(jdir, ignore_errors=True)
    emit(f"serve/latency-journal-{n}x{w * 32}xT{t_steps}r{n_req}", None,
         ";".join(f"{k}={j_st[k]:.3f}" for k in lat_keys)
         + f";journal_syncs={j_st['journal_syncs']}"
         + f";journal_snapshots={j_st['journal_snapshots']}")
    out[("serve-latency-journal", n, w * 32, t_steps, n_req)] = {
        k: j_st[k] for k in lat_keys}
    return out


if __name__ == "__main__":
    run()
