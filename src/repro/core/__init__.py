"""repro.core — Wenquxing 22A's contribution as a composable JAX module.

Layers (bottom-up):
  lfsr / bitpack          bit-exact PRNG + 1-bit synapse packing
  lif / stdp              streamlined LIF (C2), binary stochastic STDP (C3)
  rvsnn                   RV-SNN V1.0 instruction semantics (C1)
  encoder / preprocess    Poisson rate coding, deskew + soft threshold
  trainer                 supervised STDP + active learning (C4) on the
                          ``repro.engine`` verbs
  energy                  event-driven energy/footprint model (Fig.4/Tab.2)
"""

from repro.core.bitpack import n_words, pack, popcount, tail_mask, unpack
from repro.core.encoder import (encode_from_counter,
                                encode_from_counter_batch, poisson_encode,
                                poisson_encode_batch, quantize_intensities,
                                spike_rate)
from repro.core.lif import LIFParams, lif_params, lif_reset, lif_step
from repro.core.preprocess import deskew, preprocess, preprocess_batch, soft_threshold
from repro.core.rvsnn import SnnRegFile, snn_ls, snn_nu, snn_regfile, snn_sp, snn_step, snn_su
from repro.core.stdp import STDPParams, init_weights, ltd_prob_from_wexp, stdp_params, stdp_update
from repro.core.trainer import SNNModel, SNNTrainConfig, accuracy, classify, train

__all__ = [k for k in dir() if not k.startswith("_")]
