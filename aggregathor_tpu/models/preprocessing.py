"""Train-time input preprocessing (augmentation) registry.

Parity with the reference's slim ``preprocessing_factory`` selection
(experiments/slims.py:98-111 and cnnet.py's ``preprocessing`` arg, default
"cifarnet"): experiments accept ``preprocessing:<name>`` and apply the named
augmentation to training batches only (evaluation stays deterministic).

Implementations are numpy-side, applied inside the worker-batch iterator
(the host is where the reference's preprocessing threads ran too).  Each
worker's augmentation stream draws from its own generator keyed by
``(seed, tag, worker)`` — like ``WorkerBatchIterator``'s sample streams,
worker w's augmented data is independent of ``nb_workers`` and batch size,
so runs stay comparable across worker counts.  Transforms may mutate their
input: the iterator hands out a fresh (fancy-indexed) array every batch.

- ``none`` / ``lenet``: identity.
- ``cifarnet``: 4-pixel reflect pad, random crop back to size, random
  horizontal flip — the crop+flip core of slim's cifarnet_preprocessing
  (its brightness/contrast jitter is omitted, documented simplification).
- ``inception`` / ``vgg``: random horizontal flip (the full scale/aspect
  distortion pipelines are not reproduced for the synthetic stand-ins;
  flip is the shared core).

Each factory takes a seed and returns a ``transform(bx, by) -> (bx, by)``
over worker-major blocks, suitable for ``WorkerBatchIterator(transform=...)``.
"""

import numpy as np

from ..utils import UserException


class _PerWorkerRng:
    """Lazy per-worker generators: worker w's stream is f(seed, tag, w) only."""

    def __init__(self, seed, tag):
        self.seed = int(seed)
        self.tag = int(tag)
        self._rngs = {}

    def get(self, worker):
        if worker not in self._rngs:
            self._rngs[worker] = np.random.default_rng([self.seed, self.tag, worker])
        return self._rngs[worker]


def stateless(transform):
    """Declare ``transform`` stateless: its output depends only on its
    inputs — no RNG draws, no call-count state.  The batch iterator then
    skips it entirely on resume fast-forward (``WorkerBatchIterator.skip``
    advances only the index streams — seconds per thousand skipped steps
    saved) and applies it per-slice on the gathered ``next_many`` stack.
    Stateful transforms (the per-worker augmentation streams below,
    poisoning) must NOT be marked: their streams advance per batch."""
    transform.stateless = True
    return transform


def none_preprocessing(seed=0):
    return stateless(lambda bx, by: (bx, by))


def cifarnet_preprocessing(seed=0, pad=4):
    rngs = _PerWorkerRng(seed, 0xC1FA)

    def transform(bx, by):
        bx = np.asarray(bx)
        nb_workers, batch, height, width = bx.shape[:4]
        out = np.empty_like(bx)
        for w in range(nb_workers):
            rng = rngs.get(w)
            padded = np.pad(bx[w], ((0, 0), (pad, pad), (pad, pad), (0, 0)), mode="reflect")
            ox = rng.integers(0, 2 * pad + 1, size=batch)
            oy = rng.integers(0, 2 * pad + 1, size=batch)
            rows = ox[:, None, None] + np.arange(height)[None, :, None]
            cols = oy[:, None, None] + np.arange(width)[None, None, :]
            images = padded[np.arange(batch)[:, None, None], rows, cols, :]
            mask = rng.random(batch) < 0.5
            images[mask] = images[mask, :, ::-1]
            out[w] = images
        return out, by

    return transform


def flip_preprocessing(seed=0):
    rngs = _PerWorkerRng(seed, 0xF11B)

    def transform(bx, by):
        bx = np.asarray(bx)
        for w in range(bx.shape[0]):
            mask = rngs.get(w).random(bx.shape[1]) < 0.5
            bx[w, mask] = bx[w, mask][:, :, ::-1]
        return bx, by

    return transform


PREPROCESSING = {
    "none": none_preprocessing,
    "cifarnet": cifarnet_preprocessing,
    "inception": flip_preprocessing,
    "vgg": flip_preprocessing,
    "lenet": none_preprocessing,
}


# --------------------------------------------------------------------- #
# Device-side tier: the same augmentations as jnp transforms running
# INSIDE the jitted training step (engine ``batch_transform``), so the host
# input path is just a gather + transfer.  This is the TPU-idiomatic home
# for per-sample augmentation (VPU work fused into the step, zero host
# cost), where the reference necessarily burned CPU threads on it (slim
# preprocessing ran on the input pipeline's fetcher threads,
# experiments/cnnet.py:115-146).
#
# The crop slices nothing per image: an offset takes 2*pad+1 values an axis,
# so the crop is a choice among that many STATIC row shifts of the padded
# batch and then as many static column shifts, each under a select on the
# image's own offset.  Static slices and selects fuse into elementwise loops
# in whatever layout the step keeps the batch; a per-image dynamic_slice
# under the engine's vmap over workers compiled on the v5e to a ``while`` of
# one slice an image, a fifth of config 2's step (PERF.md, PR 28).  The
# output is the per-image crop's bit for bit (tests/test_preprocessing.py).
#
# Keying discipline matches the host tier: the engine derives the key from
# (run seed, step, GLOBAL worker index), so worker w's augmentation stream
# is independent of nb_workers and of the device it landed on, and a rerun
# reproduces it exactly.


def _device_cifarnet(pad=4):
    import jax
    import jax.numpy as jnp

    def transform(batch, key):
        img = batch["image"]
        b, h, w = img.shape[0], img.shape[1], img.shape[2]
        kc, kf = jax.random.split(key)
        padded = jnp.pad(img, ((0, 0), (pad, pad), (pad, pad), (0, 0)), mode="reflect")
        off = jax.random.randint(kc, (b, 2), 0, 2 * pad + 1)

        def shifted(x, offset, axis, size):
            """Image i's ``x[i, ..., offset[i]:offset[i] + size, ...]`` along ``axis``."""
            offset = offset[:, None, None, None]
            out = jax.lax.slice_in_dim(x, 0, size, axis=axis)
            for s in range(1, 2 * pad + 1):
                out = jnp.where(offset == s, jax.lax.slice_in_dim(x, s, s + size, axis=axis), out)
            return out

        crop = shifted(shifted(padded, off[:, 0], 1, h), off[:, 1], 2, w)
        flip = jax.random.bernoulli(kf, 0.5, (b,))
        out = jnp.where(flip[:, None, None, None], crop[:, :, ::-1, :], crop)
        return dict(batch, image=out)

    return transform


def _device_flip():
    import jax
    import jax.numpy as jnp

    def transform(batch, key):
        img = batch["image"]
        flip = jax.random.bernoulli(key, 0.5, (img.shape[0],))
        out = jnp.where(flip[:, None, None, None], img[:, :, ::-1, :], img)
        return dict(batch, image=out)

    return transform


DEVICE_PREPROCESSING = {
    "none": lambda: None,
    "lenet": lambda: None,
    "cifarnet": _device_cifarnet,
    "inception": _device_flip,
    "vgg": _device_flip,
}


def device_transform(name):
    """The jnp in-step transform for ``name`` (None when it is the identity)."""
    if name not in DEVICE_PREPROCESSING:
        raise UserException(
            "Unknown preprocessing %r (accepted: %s)" % (name, ", ".join(sorted(DEVICE_PREPROCESSING)))
        )
    return DEVICE_PREPROCESSING[name]()


def check(name):
    """Validate a preprocessing name at arg-parse time (fail fast)."""
    if name not in PREPROCESSING:
        raise UserException(
            "Unknown preprocessing %r (accepted: %s)" % (name, ", ".join(sorted(PREPROCESSING)))
        )
    return name


def instantiate(name, seed=0):
    return PREPROCESSING[check(name)](seed)


def default_for(model_name):
    """slim preprocessing_factory's model-name-keyed defaults
    (external/slim/preprocessing/preprocessing_factory.py): lenet/cifarnet
    keep their own pipelines, vgg/resnet use vgg, everything else inception."""
    if model_name.startswith(("lenet",)):
        return "lenet"
    if model_name.startswith(("cifarnet",)):
        return "cifarnet"
    if model_name.startswith(("vgg", "resnet")):
        return "vgg"
    return "inception"
