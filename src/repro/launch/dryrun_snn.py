import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Dry-run + §Perf hillclimb C for the paper's own workload.

Production-scale Wenquxing 22A deployment: a 4096-neuron active-learning
ensemble (102 x the paper's 40-neuron network) classifying a 4096-sample
batch (72 Poisson cycles each), plus an online-STDP training stream —
sharded population x batch over the 16x16 / 2x16x16 production meshes
(neurons -> model, batch -> data; every neuron row is independent, so
population parallelism is exact).

Two variants quantify the paper's central design choice on TPU:

  packed   (this work): 1-bit synapses in uint32 lanes, AND+popcount
  unpacked (naive port): 0/1 weights as int8, counts via dense matmul

Usage:  python -m repro.launch.dryrun_snn [--mesh pod|multipod|both]
"""  # noqa: E402

import argparse  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs.wenquxing_snn import WENQUXING_22A  # noqa: E402
from repro.core.bitpack import n_words  # noqa: E402
from repro.core.lif import LIFParams  # noqa: E402
from repro.core.rvsnn import SnnRegFile  # noqa: E402
from repro.engine import SNNEngine, train_stream  # noqa: E402
from repro.launch.dryrun import load_results, save_results  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.roofline import analyze  # noqa: E402

N_NEURONS = 4096
N_INPUTS = 784
BATCH = 4096
T = WENQUXING_22A.n_steps
STREAM = 8  # online-training samples per lowered step

LIF = LIFParams(jnp.int32(WENQUXING_22A.threshold),
                jnp.int32(WENQUXING_22A.leak))


# --- packed (paper-faithful) ----------------------------------------------------

def infer_packed(weights, spike_trains):
    """weights u32[N, W]; spike_trains u32[B, T, W] -> counts i32[B, N]."""
    return SNNEngine(WENQUXING_22A.plan()).infer(weights, spike_trains)


def train_packed(weights, lfsr_state, spike_trains, teach):
    """Online STDP over a sample stream (sequential, as in hardware).

    spike_trains u32[S, T, W]; teach i32[S, N]."""
    rf = SnnRegFile(spike=jnp.zeros((weights.shape[1],), jnp.uint32),
                    v=jnp.zeros((weights.shape[0],), jnp.int32),
                    lfsr=lfsr_state, weights=weights)
    rf2, counts = train_stream(SNNEngine(WENQUXING_22A.plan()), rf,
                               spike_trains, teach)
    return rf2.weights, rf2.lfsr, counts


# --- unpacked (naive port baseline) ---------------------------------------------

def infer_unpacked(weights8, spikes8):
    """weights8 i8[N, 784]; spikes8 i8[B, T, 784] -> counts i32[B, N].

    The dynamics are identical; the synaptic AND+count becomes a dense
    int matmul — what a direct JAX port without the paper's 1-bit
    bit-packing would do."""
    def sample(train):
        def cycle(v, spk):
            counts = jnp.einsum("i,ni->n", spk.astype(jnp.int32),
                                weights8.astype(jnp.int32))
            v2 = v + counts
            fired = v2 >= LIF.threshold
            v3 = jnp.where(fired, 0, jnp.maximum(v2 - LIF.leak, 0))
            return v3, fired
        _, fired = jax.lax.scan(
            cycle, jnp.zeros((weights8.shape[0],), jnp.int32), train)
        return fired.astype(jnp.int32).sum(0)
    return jax.vmap(sample)(spikes8)


def lower_snn(kind: str, multi_pod: bool, packed: bool) -> dict:
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = 1
    for v in mesh.shape.values():
        chips *= v
    dp = ("pod", "data") if multi_pod else ("data",)
    W = n_words(N_INPUTS)

    t0 = time.perf_counter()
    if kind == "infer":
        if packed:
            w_s = jax.ShapeDtypeStruct((N_NEURONS, W), jnp.uint32)
            s_s = jax.ShapeDtypeStruct((BATCH, T, W), jnp.uint32)
            fn = infer_packed
        else:
            w_s = jax.ShapeDtypeStruct((N_NEURONS, N_INPUTS), jnp.int8)
            s_s = jax.ShapeDtypeStruct((BATCH, T, N_INPUTS), jnp.int8)
            fn = infer_unpacked
        w_sh = NamedSharding(mesh, P("model", None))
        s_sh = NamedSharding(mesh, P(dp, None, None))
        lowered = jax.jit(fn, in_shardings=(w_sh, s_sh)).lower(w_s, s_s)
    else:  # train (packed only — the 1-bit LTP/LTD has no unpacked twin)
        w_s = jax.ShapeDtypeStruct((N_NEURONS, W), jnp.uint32)
        l_s = jax.ShapeDtypeStruct((N_NEURONS, W), jnp.uint32)
        s_s = jax.ShapeDtypeStruct((STREAM, T, W), jnp.uint32)
        t_s = jax.ShapeDtypeStruct((STREAM, N_NEURONS), jnp.int32)
        row = NamedSharding(mesh, P("model", None))
        rep = NamedSharding(mesh, P())
        tch = NamedSharding(mesh, P(None, "model"))
        lowered = jax.jit(
            train_packed, in_shardings=(row, row, rep, tch),
            donate_argnums=(0, 1)).lower(w_s, l_s, s_s, t_s)
    compiled = lowered.compile()
    dt = time.perf_counter() - t0

    mem = compiled.memory_analysis()
    rl = analyze(compiled, chips)
    peak = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    return {
        "arch": "wenquxing-22a-x102", "shape": f"snn_{kind}",
        "mesh": "2x16x16" if multi_pod else "16x16", "chips": chips,
        "status": "ok", "compile_s": round(dt, 1),
        "variant": "packed" if packed else "unpacked",
        "peak_bytes_per_device": peak,
        "fits_16GB": bool(peak < 16e9),
        "roofline": rl.summary(),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="both",
                    choices=["pod", "multipod", "both"])
    ap.add_argument("--out", default="experiments/dryrun_results.json")
    args = ap.parse_args()
    from pathlib import Path
    out = Path(args.out)
    results = load_results(out)
    meshes = {"pod": [False], "multipod": [True],
              "both": [False, True]}[args.mesh]
    for mp in meshes:
        for kind in ("infer", "train"):
            for packed in ((True, False) if kind == "infer" else (True,)):
                key = (f"wenquxing-22a-x102|snn_{kind}|"
                       f"{'2x16x16' if mp else '16x16'}"
                       f"{'' if packed else '#unpacked'}")
                print(f"[cell] {key}", flush=True)
                res = lower_snn(kind, mp, packed)
                rl = res["roofline"]
                print(f"  -> t_c={rl['t_compute_s']:.4f} "
                      f"t_m={rl['t_memory_s']:.4f} "
                      f"t_coll={rl['t_collective_s']:.4f} "
                      f"dom={rl['dominant']} "
                      f"peak={res['peak_bytes_per_device']/1e9:.2f}GB",
                      flush=True)
                results[key] = res
                save_results(out, results)


if __name__ == "__main__":
    main()
