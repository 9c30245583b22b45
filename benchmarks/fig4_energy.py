"""Paper Figure 4: power comparison Wenquxing 22A vs ODIN.

Paper: 5.055 W vs 25.949 W on the same Alveo U250 (5.13x).  This
container cannot measure FPGA watts; we run the event-driven energy
model (repro.core.energy) on REAL spike statistics from the trained
network — fused-pipeline machine vs decoupled-accelerator machine — and
report modeled energy + the ratio.  Constants documented in energy.py.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import digits_dataset, emit
from repro.configs.wenquxing_snn import WENQUXING_22A
from repro.core.bitpack import unpack
from repro.core.encoder import poisson_encode_batch
from repro.core.energy import EnergyConstants, count_events, energy
from repro.core.trainer import train
from repro.engine import SNNEngine

PAPER_RATIO = 25.949 / 5.055  # 5.13x


def run() -> dict:
    tr, tr_lab, te, te_lab = digits_dataset(n_train=1000, n_test=200)
    cfg = dataclasses.replace(WENQUXING_22A, n_neurons=40)
    model = train(cfg, tr, tr_lab)
    st = poisson_encode_batch(jax.random.key(7), jnp.asarray(te),
                              cfg.n_steps)
    # real spike statistics over the test presentations
    in_spikes = int(unpack(st.reshape(-1, st.shape[-1]), 784).sum())
    counts = np.asarray(SNNEngine(cfg.plan()).infer(model.weights, st))
    post = int(counts.sum())
    n_samples = st.shape[0]
    k = EnergyConstants()

    results = {}
    for machine in ("fused", "decoupled"):
        ev = count_events(cfg.n_neurons, cfg.n_inputs,
                          cfg.n_steps * n_samples, in_spikes, post,
                          machine)
        e = energy(ev, k, machine)
        results[machine] = e
        emit(f"fig4/{machine}", e["time_s"] * 1e6,
             f"modeled_E={e['total_J']:.3e}J;avg_P={e['avg_power_W']:.3f}W")
    ratio = results["decoupled"]["total_J"] / results["fused"]["total_J"]
    emit("fig4/ratio-decoupled-over-fused", 0.0,
         f"modeled={ratio:.2f}x;paper={PAPER_RATIO:.2f}x")
    return {"ratio": ratio}


if __name__ == "__main__":
    run()
