"""The one general generator of training traffic. A mix is a data file
(``traffic/<mix>.json``) of parameters; nothing here knows a mix by name.

Parameters of a mix:

* ``placement``: ``"device"`` (batches are on the chips, under the
  executor's own batch sharding, before the clock starts) or ``"host"``
  (numpy batches that ``fit`` has to stage and copy at every step);
* ``distinct_batches``: how many different batches are made and cycled;
* ``label_classes``, ``template_scale``, ``noise_scale``: every class
  carries a template of its own under per-row noise, so that rows all
  differ and labels follow from pixels;
* ``warmup_steps``: steps of the warm-up epoch (a multiple of the
  ``steps_per_dispatch`` in use, or the tail would compile a second
  program); the first three are the ones compared with the reference.

Every seed makes the same amount of work: the sizes are fixed by the
configuration and the mix, only the values follow the seed.
"""
from __future__ import annotations

import json
import os


def load_mix(bench_dir, name):
    with open(os.path.join(bench_dir, "traffic", name + ".json")) as f:
        mix = json.load(f)
    if mix["placement"] not in ("device", "host"):
        raise ValueError("traffic %s: unknown placement %r"
                         % (name, mix["placement"]))
    return mix


def make_batches(mix, cfg, seed, sharding=None):
    """``distinct_batches`` pairs (data, label) of float32 arrays, made
    from the seed in one jitted call on the device; ``sharding`` is the
    batch sharding of a cell on several chips. For ``placement: host``
    they are fetched to numpy afterwards."""
    import jax
    import jax.numpy as jnp

    n = int(mix["distinct_batches"])
    batch = int(cfg["batch_size"])
    shape = tuple(cfg["image_shape"])
    classes = min(int(mix["label_classes"]), int(cfg["num_classes"]))

    def make(key):
        k_t, k_n = jax.random.split(key)
        templates = jax.random.normal(k_t, (classes,) + shape, jnp.float32) \
            * mix["template_scale"]
        out = []
        for i in range(n):
            label = (jnp.arange(batch) + i) % classes
            noise = jax.random.normal(jax.random.fold_in(k_n, i),
                                      (batch,) + shape, jnp.float32)
            out.append((templates[label] + noise * mix["noise_scale"],
                        label.astype(jnp.float32)))
        return out

    # another stream than the weights', from the same seed
    key = jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)), 0x7a11)
    batches = jax.jit(make, out_shardings=sharding)(key)
    if mix["placement"] == "host":
        import numpy as np
        batches = [(np.asarray(d), np.asarray(l)) for d, l in batches]
    return batches
