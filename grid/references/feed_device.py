"""Which rows each worker trains on at each step, restated from the program's
documented stream (engine.build_sampled_multi_step, models/preprocessing.py):
worker w's draw at step s is a function of (run key, s, w) alone.

    step key   = fold_in(run key, s)
    rows       = randint(fold_in(fold_in(step key, w), 4), (batch,), 0, examples)
    augment by = fold_in(fold_in(step key, w), 3)

The run key is ``PRNGKey(seed)``.  The augmentations are restated here too.
"""

import functools

import jax
import jax.numpy as jnp


def _cifarnet(images, key, pad=4):
    """Reflect-pad by 4, crop back at a random offset, flip half of them."""
    count, height, width, channels = images.shape
    crop_key, flip_key = jax.random.split(key)
    padded = jnp.pad(images, ((0, 0), (pad, pad), (pad, pad), (0, 0)), mode="reflect")
    offsets = jax.random.randint(crop_key, (count, 2), 0, 2 * pad + 1)
    rows = offsets[:, 0, None] + jnp.arange(height)[None, :]
    cols = offsets[:, 1, None] + jnp.arange(width)[None, :]
    cropped = padded[jnp.arange(count)[:, None, None], rows[:, :, None], cols[:, None, :]]
    flip = jax.random.bernoulli(flip_key, 0.5, (count,))
    return jnp.where(flip[:, None, None, None], cropped[:, :, ::-1, :], cropped)


def _flip(images, key):
    flip = jax.random.bernoulli(key, 0.5, (images.shape[0],))
    return jnp.where(flip[:, None, None, None], images[:, :, ::-1, :], images)


AUGMENT = {"none": lambda images, key: images, "cifarnet": _cifarnet, "flip": _flip}


@functools.partial(jax.jit, static_argnames=("batch_size", "augment"))
def worker_batch(dataset, run_key, step, worker, *, batch_size, augment):
    """(images, labels) of ``worker`` at ``step``."""
    step_key = jax.random.fold_in(run_key, step)
    worker_key = jax.random.fold_in(step_key, worker)
    rows = jax.random.randint(jax.random.fold_in(worker_key, 4), (batch_size,), 0,
                              dataset["image"].shape[0])
    images = AUGMENT[augment](dataset["image"][rows], jax.random.fold_in(worker_key, 3))
    return images, dataset["label"][rows]
