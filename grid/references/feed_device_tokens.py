"""Which token rows each worker trains on at each step and how they are
noised, restated from the program's documented stream
(engine.build_sampled_multi_step, models/sdar.py ``noise``): worker w's draw at
step s is a function of (run key, s, w) alone.

    step key   = fold_in(run key, s)
    rows       = randint(fold_in(fold_in(step key, w), 4), (batch,), 0, rows held)
    noise key  = fold_in(fold_in(step key, w), 3), split in two:
      t        = uniform(first, (batch,), 0.001, 1)       once a sequence
      masked   = uniform(second, (batch, L)) < t          each token on its own
      x_t      = mask id where masked, else x_0

The run key is ``PRNGKey(seed)``.  ``augment`` is the configuration's
``"mask_token_id=<id>"``.  Returns ``({"noisy": x_t, "t": t}, x_0)``, what
grid/references/sdar_moe.py ``loss`` takes as inputs and targets.
"""

import functools

import jax
import jax.numpy as jnp

T_MIN = 1e-3


@functools.partial(jax.jit, static_argnames=("batch_size", "augment"))
def worker_batch(dataset, run_key, step, worker, *, batch_size, augment):
    name, _, mask_id = augment.partition("=")
    if name != "mask_token_id":
        raise SystemExit("feed_device_tokens: augment %r is not mask_token_id=<id>" % augment)
    worker_key = jax.random.fold_in(jax.random.fold_in(run_key, step), worker)
    rows = jax.random.randint(jax.random.fold_in(worker_key, 4), (batch_size,), 0,
                              dataset["tokens"].shape[0])
    clean = dataset["tokens"][rows]
    t_key, mask_key = jax.random.split(jax.random.fold_in(worker_key, 3))
    t = jax.random.uniform(t_key, (batch_size,), jnp.float32, T_MIN, 1.0)
    masked = jax.random.uniform(mask_key, clean.shape, jnp.float32) < t[:, None]
    return {"noisy": jnp.where(masked, int(mask_id), clean), "t": t}, clean
