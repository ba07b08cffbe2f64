"""The generator of token traffic for a language model's training cells. A
mix is a data file (``traffic/<mix>.json``) of parameters; nothing here
knows a mix by name.

Parameters of a mix, beside those ``harness/traffic.py`` reads
(``placement``, ``distinct_batches``, ``warmup_steps``):

* ``zipf_exponent``: ids are drawn with probability proportional to
  ``rank ** -exponent`` over the rows of the vocabulary that the
  configuration holds (``vocab_size``), id 0 the most frequent: the
  shape of word frequencies, so that a few rows of the embedding and of
  the head take most of the traffic.

A batch is ``batch_size`` sequences of ``sequence_length`` ids, each one
document (no packing), and its labels are the next ids; both are float32,
the way an MXNet iterator feeds ids. Every seed makes the same amount of
work: the sizes are fixed by the configuration and the mix, only the values
follow the seed.
"""
from __future__ import annotations


def seed_key(seed):
    """A key for any whole number: the low 31 bits seed it, what is above
    them is folded in, so that 2**31 + 7 is another stream than 7."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)),
                              seed >> 31)


def make_token_batches(mix, cfg, seed):
    """``distinct_batches`` pairs (ids, next ids) of float32 arrays
    (batch_size, sequence_length), made from the seed in one jitted call on
    the device; for ``placement: host`` fetched to numpy afterwards."""
    import jax
    import jax.numpy as jnp

    n = int(mix["distinct_batches"])
    b, t = int(cfg["batch_size"]), int(cfg["sequence_length"])
    vocab = int(cfg["vocab_size"])

    def make(key):
        p = jnp.arange(1, vocab + 1, dtype=jnp.float32) \
            ** -float(mix["zipf_exponent"])
        cdf = jnp.cumsum(p) / jnp.sum(p)
        u = jax.random.uniform(key, (n, b, t + 1))
        ids = jnp.minimum(jnp.searchsorted(cdf, u), vocab - 1)
        ids = ids.astype(jnp.float32)
        return [(ids[i, :, :-1], ids[i, :, 1:]) for i in range(n)]

    # another stream than the weights', from the same seed
    batches = jax.jit(make)(jax.random.fold_in(seed_key(seed), 0x70c5))
    if mix["placement"] == "host":
        import numpy as np
        batches = [(np.asarray(d), np.asarray(l)) for d, l in batches]
    return batches
