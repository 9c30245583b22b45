"""Plain reference of Diehl & Cook's network as the system trains it,
in ``jax.numpy``: one population of excitatory neurons, each with an
adaptive threshold, and one inhibitory relay per neuron.

Per sample, with ``v`` and the last cycle's spikes ``f`` at 0, and per
cycle ``t`` with the input spikes ``pre`` drawn by
:func:`chip.reference.encode`:

* ``I_j = popcount(pre & w_j)``;
* ``u_j = v_j + I_j - w_inh * (F - f_j)``, ``F`` the sum of ``f``: each
  inhibitory neuron relays its partner's spike one cycle late onto
  every other excitatory neuron;
* fire iff ``u_j >= threshold + θ_j``; then ``v_j = 0`` if fired, else
  ``max(u_j - leak, 0)``; ``θ_j += theta_plus`` on a spike;
* STDP of :func:`chip.reference.stdp` on the fired rows.

θ, the weights and the LFSR lanes carry from sample to sample.  Integer
arithmetic throughout, so agreement is equality.  It runs on the chip,
where integer arithmetic is exact too: a replay of one call (64 samples
of 350 cycles over 6,400 x 784 synapses) takes a fraction of a second
there and minutes on a CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chip.reference import encode, stdp


def _popcount_and(pre, weights):
    both = pre[None, :] & weights
    return jnp.sum(jax.lax.population_count(both).astype(jnp.int32),
                   axis=-1)


@functools.partial(jax.jit, static_argnames=(
    "t_steps", "threshold", "leak", "w_exp", "gain", "n_syn", "ltp_prob",
    "w_inh", "theta_plus"))
def train_stream(weights, lfsr, theta, intensities, seeds, *, t_steps: int,
                 threshold: int, leak: int, w_exp: int, gain: int,
                 n_syn: int, ltp_prob: int, w_inh: int, theta_plus: int):
    """Online STDP of one population over N samples.

    weights, lfsr uint32[n, W]; theta int32[n]; intensities uint8[N,
    n_in] and seeds uint32[N].  Returns the final (weights, lfsr, theta).
    """
    pad = weights.shape[-1]
    zeros = jnp.zeros(weights.shape[:1], jnp.int32)

    def sample(carry, inp):
        x, sd = inp
        win = encode(sd[None], x[None], t_steps)[0]
        win = jnp.pad(win, ((0, 0), (0, pad - win.shape[-1])))

        def cycle(c, pre):
            w, lf, th, v, f = c
            u = v + _popcount_and(pre, w) - w_inh * (jnp.sum(f) - f)
            fired = u >= threshold + th
            v = jnp.where(fired, 0, jnp.maximum(u - leak, 0))
            spikes = fired.astype(jnp.int32)
            w, lf = stdp(w, lf, pre, fired, ltp_prob=jnp.uint32(ltp_prob),
                         w_exp=w_exp, gain=gain, n_syn=n_syn)
            return (w, lf, th + theta_plus * spikes, v, spikes), None

        (w, lf, th, _, _), _ = jax.lax.scan(cycle, carry + (zeros, zeros),
                                            win)
        return (w, lf, th), None

    out, _ = jax.lax.scan(sample, (weights, lfsr, theta),
                          (intensities, seeds))
    return out
