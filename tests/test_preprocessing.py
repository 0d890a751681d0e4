"""Train-time preprocessing (augmentation) tests: slim preprocessing_factory parity."""

import re

import numpy as np
import pytest

from aggregathor_tpu.models import preprocessing
from aggregathor_tpu.utils import UserException


def _block(seed=0, n=2, b=3, size=32):
    rng = np.random.default_rng(seed)
    bx = rng.random((n, b, size, size, 3)).astype(np.float32)
    by = rng.integers(0, 10, size=(n, b)).astype(np.int32)
    return bx, by


def test_none_is_identity():
    bx, by = _block()
    tx, ty = preprocessing.instantiate("none")(bx, by)
    np.testing.assert_array_equal(tx, bx)
    np.testing.assert_array_equal(ty, by)


def test_cifarnet_crop_flip_properties():
    bx, by = _block()
    transform = preprocessing.instantiate("cifarnet", seed=1)
    tx, ty = transform(bx.copy(), by)
    assert tx.shape == bx.shape and tx.dtype == bx.dtype
    np.testing.assert_array_equal(ty, by)          # labels untouched
    assert not np.array_equal(tx, bx)              # something moved
    # values all come from the source images (crop of reflect-pad)
    assert tx.min() >= bx.min() - 1e-6 and tx.max() <= bx.max() + 1e-6
    # deterministic per seed
    t2 = preprocessing.instantiate("cifarnet", seed=1)(bx.copy(), by)[0]
    np.testing.assert_array_equal(tx, t2)
    # different under a different seed
    t3 = preprocessing.instantiate("cifarnet", seed=2)(bx.copy(), by)[0]
    assert not np.array_equal(tx, t3)


def test_worker_stream_independent_of_worker_count():
    """Worker w's augmentation stream is f(seed, w) only — the same images
    for worker 0 come out identically whether 2 or 4 workers run (the same
    guarantee WorkerBatchIterator gives for the raw sample streams)."""
    bx4, by4 = _block(seed=5, n=4)
    bx2, by2 = bx4[:2].copy(), by4[:2].copy()
    t4 = preprocessing.instantiate("cifarnet", seed=9)(bx4.copy(), by4)[0]
    t2 = preprocessing.instantiate("cifarnet", seed=9)(bx2, by2)[0]
    np.testing.assert_array_equal(t4[:2], t2)
    f4 = preprocessing.instantiate("inception", seed=9)(bx4.copy(), by4)[0]
    f2 = preprocessing.instantiate("inception", seed=9)(bx4[:2].copy(), by4[:2])[0]
    np.testing.assert_array_equal(f4[:2], f2)


def test_flip_only_flips():
    bx, by = _block(seed=3)
    tx, _ = preprocessing.instantiate("inception", seed=0)(bx.copy(), by)
    flat_in = bx.reshape(-1, *bx.shape[2:])
    flat_out = tx.reshape(-1, *tx.shape[2:])
    for i in range(flat_in.shape[0]):
        same = np.array_equal(flat_out[i], flat_in[i])
        flipped = np.array_equal(flat_out[i], flat_in[i, :, ::-1])
        assert same or flipped


def test_unknown_preprocessing_rejected_at_init():
    from aggregathor_tpu import models

    with pytest.raises(UserException):
        preprocessing.check("nope")
    with pytest.raises(UserException):  # fails fast at experiment construction
        models.instantiate("cnnet", ["preprocessing:nope"])


def test_model_keyed_defaults():
    assert preprocessing.default_for("lenet") == "lenet"
    assert preprocessing.default_for("cifarnet") == "cifarnet"
    assert preprocessing.default_for("vgg_16") == "vgg"
    assert preprocessing.default_for("resnet_v2_50") == "vgg"
    assert preprocessing.default_for("inception_v3") == "inception"
    assert preprocessing.default_for("mobilenet_v2") == "inception"


def test_experiments_accept_preprocessing_args():
    from aggregathor_tpu import models

    exp = models.instantiate("cnnet", [
        "batch-size:4", "preprocessing:none", "nb-fetcher-threads:4", "nb-batcher-threads:2",
    ])
    batch = next(exp.make_train_iterator(2, seed=0))
    assert batch["image"].shape[:2] == (2, 4)
    zoo = models.instantiate("slim-lenet-cifar10", ["batch-size:2"])
    assert zoo.preprocessing == "lenet"  # model-keyed default, not dataset-keyed
    zb = next(zoo.make_train_iterator(2, seed=0))
    assert zb["image"].shape[:2] == (2, 2)


# --------------------------------------------------------------------- #
# Device tier (in-step jnp augmentation) and the vectorized K-batch fetch


def test_device_cifarnet_properties():
    import jax

    transform = preprocessing.device_transform("cifarnet")
    rng = np.random.default_rng(0)
    img = rng.random((5, 32, 32, 3)).astype(np.float32)
    batch = {"image": img, "label": np.arange(5, dtype=np.int32)}
    out = jax.jit(transform)(batch, jax.random.PRNGKey(0))
    assert out["image"].shape == img.shape
    np.testing.assert_array_equal(np.asarray(out["label"]), batch["label"])
    x = np.asarray(out["image"])
    assert not np.array_equal(x, img)  # something moved
    # crop-of-reflect-pad: values all come from the source
    assert x.min() >= img.min() - 1e-6 and x.max() <= img.max() + 1e-6
    # deterministic per key, different across keys
    x2 = np.asarray(jax.jit(transform)(batch, jax.random.PRNGKey(0))["image"])
    np.testing.assert_array_equal(x, x2)
    x3 = np.asarray(jax.jit(transform)(batch, jax.random.PRNGKey(7))["image"])
    assert not np.array_equal(x, x3)


def _per_image_crop_flip(images, key, pad):
    """``_device_cifarnet`` restated one image at a time: index arithmetic on
    the same ``randint`` draw (as ``grid/references/feed_device.py`` gathers
    it), then the same flip."""
    import jax

    count, height, width = images.shape[:3]
    crop_key, flip_key = jax.random.split(key)
    offsets = np.asarray(jax.random.randint(crop_key, (count, 2), 0, 2 * pad + 1))
    flip = np.asarray(jax.random.bernoulli(flip_key, 0.5, (count,)))
    padded = np.pad(images, ((0, 0), (pad, pad), (pad, pad), (0, 0)), mode="reflect")
    out = np.empty_like(images)
    for i, (oy, ox) in enumerate(offsets):
        crop = padded[i, oy:oy + height, ox:ox + width]
        out[i] = crop[:, ::-1] if flip[i] else crop
    return out


@pytest.mark.parametrize("seed", [0, 2_654_435_761])
@pytest.mark.parametrize("shape", [(32, 32, 3), (12, 20, 3)])
@pytest.mark.parametrize("pad", [1, 4])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_device_cifarnet_is_the_per_image_crop(dtype, pad, shape, seed):
    """Under ``vmap`` over workers, as the engine's ``aug_one`` calls it: the
    crop is a copy, so every bit of every image is the per-image crop's."""
    import jax
    import jax.numpy as jnp

    workers, count = 3, 37  # offsets take (2 pad + 1)^2 values: 37 draws reach most rows and columns
    images = jax.random.uniform(jax.random.PRNGKey(1), (workers, count) + shape).astype(dtype)
    keys = jax.random.split(jax.random.PRNGKey(seed), workers)
    transform = preprocessing._device_cifarnet(pad)
    out = jax.jit(jax.vmap(lambda im, key: transform({"image": im}, key)["image"]))(images, keys)
    assert out.dtype == jnp.dtype(dtype) and out.shape == images.shape
    host = np.asarray(images.astype(jnp.float32))  # exact: widening only
    got = np.asarray(out.astype(jnp.float32))
    for worker in range(workers):
        np.testing.assert_array_equal(got[worker], _per_image_crop_flip(host[worker], keys[worker], pad))


def test_device_cifarnet_neither_gathers_nor_loops():
    """Static slices under selects: no per-image slice for the compiler to
    make a loop of (8192 iterations a step at config 2's batch)."""
    import jax
    import jax.numpy as jnp

    transform = preprocessing.device_transform("cifarnet")
    batch = {"image": jnp.zeros((2, 6, 32, 32, 3), jnp.bfloat16)}
    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    text = str(jax.make_jaxpr(jax.vmap(transform))(batch, keys))  # nested jaxprs print inline
    assert " slice[" in text and "select_n" in text
    assert not re.findall(r"\b(?:dynamic_slice|gather|while|scan)\b", text)


def test_device_flip_only_flips():
    import jax

    transform = preprocessing.device_transform("inception")
    rng = np.random.default_rng(1)
    img = rng.random((8, 16, 16, 3)).astype(np.float32)
    out = np.asarray(jax.jit(transform)({"image": img}, jax.random.PRNGKey(3))["image"])
    for i in range(img.shape[0]):
        assert np.array_equal(out[i], img[i]) or np.array_equal(out[i], img[i, :, ::-1])
    assert preprocessing.device_transform("none") is None
    assert preprocessing.device_transform("lenet") is None


def test_next_many_matches_successive_next():
    from aggregathor_tpu import models

    ex = models.instantiate("cnnet", ["batch-size:6", "augment:device"])
    a = ex.make_train_iterator(3, seed=4)
    b = ex.make_train_iterator(3, seed=4)
    many = a.next_many(4)
    assert many["image"].shape[:3] == (4, 3, 6)
    for step in range(4):
        one = next(b)
        np.testing.assert_array_equal(many["image"][step], one["image"])
        np.testing.assert_array_equal(many["label"][step], one["label"])
    # host-transform iterators fall back to the per-batch path, same result
    ex_host = models.instantiate("cnnet", ["batch-size:6"])
    ah = ex_host.make_train_iterator(2, seed=4)
    bh = ex_host.make_train_iterator(2, seed=4)
    manyh = ah.next_many(2)
    for step in range(2):
        np.testing.assert_array_equal(manyh["image"][step], next(bh)["image"])


def test_engine_device_augment_deterministic_and_applied():
    import jax
    import optax

    from aggregathor_tpu import gars, models
    from aggregathor_tpu.parallel.engine import RobustEngine
    from aggregathor_tpu.parallel.mesh import make_mesh

    ex = models.instantiate("cnnet", ["batch-size:4", "augment:device"])
    mesh = make_mesh(nb_workers=4)
    gar = gars.instantiate("median", 4, 0)
    tx = optax.sgd(1e-2)
    batch = next(ex.make_train_iterator(4, seed=0))

    def run(transform):
        eng = RobustEngine(mesh, gar, 4, batch_transform=transform)
        state = eng.init_state(ex.init(jax.random.PRNGKey(0)), tx, seed=1)
        step = eng.build_step(ex.loss, tx)
        state, m = step(state, eng.shard_batch(batch))
        return float(m["total_loss"])

    with_aug = run(ex.device_transform())
    with_aug_again = run(ex.device_transform())
    without = run(None)
    assert with_aug == with_aug_again  # keyed by (seed, step, worker): reproducible
    assert with_aug != without  # augmentation really runs inside the step
