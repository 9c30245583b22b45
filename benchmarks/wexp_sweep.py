"""Paper §3.3: the w_exp meta-parameter sweep {128, 256, 512}.

Validates the dead-neuron claim: w_exp controls the LTD probability and
thereby the number of effective synapses; the wrong setting leaves
neurons dead (never winning for their class) and costs accuracy.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import digits_dataset, emit
from repro.configs.wenquxing_snn import WENQUXING_22A
from repro.core.bitpack import unpack
from repro.core.encoder import poisson_encode_batch
from repro.core.trainer import train
from repro.engine import SNNEngine


def run() -> dict:
    tr, tr_lab, te, te_lab = digits_dataset()
    st = poisson_encode_batch(jax.random.key(99), jnp.asarray(te),
                              WENQUXING_22A.n_steps)
    out = {}
    for wexp in (128, 256, 512):
        cfg = dataclasses.replace(WENQUXING_22A, w_exp=wexp, n_neurons=40)
        t0 = time.time()
        model = train(cfg, tr, tr_lab)
        counts = np.asarray(SNNEngine(cfg.plan()).infer(model.weights,
                                                        st))
        pred = np.asarray(model.neuron_class)[counts.argmax(1)]
        acc = float((pred == te_lab).mean())
        # dead neuron = never the argmax winner on the test set
        winners = set(counts.argmax(1).tolist())
        dead = cfg.n_neurons - len(winners)
        on_bits = unpack(model.weights, 784).sum(axis=1)
        emit(f"wexp/{wexp}", (time.time() - t0) * 1e6,
             f"CA={acc:.4f};dead={dead};mean_on={float(np.mean(np.asarray(on_bits))):.0f}")
        out[wexp] = {"acc": acc, "dead": dead}
    return out


if __name__ == "__main__":
    run()
